CREATE TABLE nexmark WITH (connector = 'bench_nexmark', feed = '{feed}');
CREATE TABLE sink (id BIGINT, name TEXT)
  WITH (connector = 'bench_sink', feed = '{feed}', type = 'sink');
INSERT INTO sink
SELECT P.id, P.name FROM (
  SELECT person.id as id, person.name as name,
         tumble(interval '10 second') as w, count(*) as c
  FROM nexmark WHERE person IS NOT NULL GROUP BY 1, 2, w
) AS P JOIN (
  SELECT auction.seller as seller, tumble(interval '10 second') as w,
         count(*) as c2
  FROM nexmark WHERE auction IS NOT NULL GROUP BY 1, w
) AS A ON P.id = A.seller AND P.w = A.w;
