CREATE TABLE nexmark WITH (connector = 'bench_nexmark', feed = '{feed}');
CREATE TABLE sink (bidder BIGINT, bid_count BIGINT,
                   session_start TIMESTAMP, session_end TIMESTAMP)
  WITH (connector = 'bench_sink', feed = '{feed}', type = 'sink');
INSERT INTO sink
SELECT bidder, bid_count, window.start AS session_start,
       window.end AS session_end
FROM (
  SELECT bid.bidder AS bidder, count(*) AS bid_count,
         session(interval '10 seconds') AS window
  FROM nexmark WHERE bid IS NOT NULL
  GROUP BY 1, window
);
