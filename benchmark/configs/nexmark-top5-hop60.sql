CREATE TABLE nexmark WITH (connector = 'bench_nexmark', feed = '{feed}');
CREATE TABLE sink (auction BIGINT, count BIGINT, row_num BIGINT)
  WITH (connector = 'bench_sink', feed = '{feed}', type = 'sink');
INSERT INTO sink
SELECT auction, count, row_num FROM (
  SELECT *, ROW_NUMBER() OVER (
    PARTITION BY window
    ORDER BY count DESC, auction DESC) AS row_num
  FROM (
    SELECT count(*) AS count, bid.auction AS auction,
           hop(interval '2 seconds', interval '60 seconds') AS window
    FROM nexmark WHERE bid is not null
    GROUP BY 2, window
  )
) WHERE row_num <= 5;
