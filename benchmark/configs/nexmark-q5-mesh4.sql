CREATE TABLE nexmark WITH (connector = 'bench_nexmark', feed = '{feed}');
CREATE TABLE sink (auction BIGINT, num BIGINT)
  WITH (connector = 'bench_sink', feed = '{feed}', type = 'sink');
INSERT INTO sink
SELECT AuctionBids.auction, AuctionBids.num
FROM (
  SELECT bid.auction as auction, count(*) AS num,
         hop(interval '2 second', interval '10 second') as window
  FROM nexmark WHERE bid IS NOT NULL
  GROUP BY 1, window
) AS AuctionBids
JOIN (
  SELECT max(CountBids.num) AS maxn, CountBids.window
  FROM (
    SELECT bid.auction as auction, count(*) AS num,
           hop(interval '2 second', interval '10 second') as window
    FROM nexmark WHERE bid IS NOT NULL
    GROUP BY 1, window
  ) AS CountBids
  GROUP BY CountBids.window
) AS MaxBids
ON AuctionBids.window = MaxBids.window
   AND AuctionBids.num >= MaxBids.maxn;
