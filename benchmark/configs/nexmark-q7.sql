CREATE TABLE nexmark WITH (connector = 'bench_nexmark', feed = '{feed}');
CREATE TABLE sink (auction BIGINT, price BIGINT, bidder BIGINT)
  WITH (connector = 'bench_sink', feed = '{feed}', type = 'sink');
INSERT INTO sink
SELECT W.auction, W.price, W.bidder FROM (
  SELECT bid.auction as auction, bid.price as price, bid.bidder as bidder,
         tumble(interval '10 second') as w, count(*) as c
  FROM nexmark WHERE bid IS NOT NULL GROUP BY 1, 2, 3, w
) AS W JOIN (
  SELECT max(bid.price) as maxprice, tumble(interval '10 second') as w
  FROM nexmark WHERE bid IS NOT NULL GROUP BY w
) AS M ON W.w = M.w AND W.price = M.maxprice;
