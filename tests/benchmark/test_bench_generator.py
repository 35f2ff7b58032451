"""The benchmark's generator: NEXmark's key distribution (Beam's
generator, which upstream Arroyo's connector ports) on the batch building
copied from the program, a stream of its own for every seed."""

import numpy as np
import pytest

from gen import nexmark as gen

from arroyo_tpu.connectors import nexmark as program


def batches(lo, hi, seed):
    ns = np.arange(lo, hi, dtype=np.int64)
    ts = gen.event_times(ns, 0, 100_000.0)
    return gen.gen_batch(ns, ts, seed), program.gen_batch(ns, ts)


@pytest.mark.parametrize("lo,hi", [(0, 4096), (50_000_000, 50_008_192),
                                   (123_457, 123_457 + 777)])
def test_what_no_query_keys_on_is_the_programs_at_seed_0(lo, hi):
    # the copied batch building: persons, event times, kinds, and every
    # field of auctions and bids but the ids drawn from the key distribution
    mine, theirs = batches(lo, hi, 0)
    assert mine.schema.equals(theirs.schema)
    assert mine.column("person").equals(theirs.column("person"))
    assert mine.column("_timestamp").equals(theirs.column("_timestamp"))
    for col, drawn in (("auction", {"seller"}),
                       ("bid", {"auction", "bidder", "price", "url"})):
        a, b = mine.column(col), theirs.column(col)
        assert a.is_valid().equals(b.is_valid())
        for f in a.type:
            if f.name not in drawn:
                assert a.field(f.name).equals(b.field(f.name)), f.name


def bids_of(lo, hi, seed):
    ns = np.arange(lo, hi, dtype=np.int64)
    is_bid, auction, bidder, price = gen.bids(ns, seed)
    return ns[is_bid], auction, bidder, price


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_a_bids_auction_is_the_hot_one_or_in_flight(seed):
    # BidGenerator.nextBid / AuctionGenerator.nextBase0AuctionId
    n, auction, _b, _p = bids_of(2_000_000, 2_200_000, seed)
    last0 = n // 50 * 3 + 2                   # newest auction, base 0
    a0 = auction - gen.FIRST_AUCTION_ID
    hot = a0 == last0 // 100 * 100
    cold = a0[~hot]
    assert 0.49 < hot.mean() < 0.52           # 1/2, and a cold draw can hit it
    assert (cold >= last0[~hot] - 100).all()
    assert (cold <= last0[~hot] + 10).all()
    assert len(np.unique(cold - last0[~hot])) == 111


@pytest.mark.parametrize("seed", [0, 7])
def test_a_bids_bidder_is_the_hot_one_or_active(seed):
    n, _a, bidder, _p = bids_of(2_000_000, 2_200_000, seed)
    last0 = n // 50
    b0 = bidder - gen.FIRST_PERSON_ID
    hot = b0 == last0 // 100 * 100 + 1
    assert 0.74 < hot.mean() < 0.76
    cold = b0[~hot] - last0[~hot]
    assert cold.min() == -999 and cold.max() == 10


def test_a_sellers_id_is_the_hot_one_or_active():
    ns = np.arange(2_000_000, 2_200_000, dtype=np.int64)
    ns = ns[(ns % 50 >= 1) & (ns % 50 < 4)]
    seller = gen._auction_fields(ns, gen.seed_key(3))[0]
    last0 = ns // 50
    s0 = seller - gen.FIRST_PERSON_ID
    hot = s0 == last0 // 100 * 100
    assert 0.73 < hot.mean() < 0.77
    cold = s0[~hot] - last0[~hot]
    assert cold.min() >= -999 and cold.max() <= 10


def test_a_windows_keys_are_nexmarks():
    # 10 s at 100k events/s: the auctions created in the window plus those
    # in flight, and a hottest auction with half of the bids of its batch
    _n, auction, _b, price = bids_of(5_000_000, 6_000_000, 11)
    keys, counts = np.unique(auction, return_counts=True)
    assert 60_000 <= len(keys) <= 60_000 + 120
    assert 700 < counts.max() < 1_000         # 1,667 events, 46/50 bids, 1/2
    assert price.min() >= 100 and price.max() <= 100_000_000


def test_the_early_stream_has_no_id_below_the_first():
    _n, auction, bidder, _p = bids_of(0, 5_000, 5)
    assert auction.min() >= gen.FIRST_AUCTION_ID
    assert bidder.min() >= gen.FIRST_PERSON_ID


def test_the_schema_is_the_programs():
    assert gen.SCHEMA.equals(program.NEXMARK_SCHEMA.schema)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_another_seed_is_another_stream(seed):
    mine, theirs = batches(50_000_000, 50_008_192, seed)
    assert not mine.column("bid").equals(theirs.column("bid"))
    # same kinds in the same places, same times: only the draws differ
    assert mine.column("_timestamp").equals(theirs.column("_timestamp"))
    assert mine.column("bid").is_valid().equals(
        theirs.column("bid").is_valid())


def test_the_same_seed_gives_the_same_stream_whatever_the_batching():
    ns = np.arange(1000, 3000, dtype=np.int64)
    ts = gen.event_times(ns, 0, 2000.0)
    whole = gen.gen_batch(ns, ts, 9)
    halves = [gen.gen_batch(ns[i:i + 1000], ts[i:i + 1000], 9)
              for i in (0, 1000)]
    assert whole.slice(0, 1000).equals(halves[0])
    assert whole.slice(1000).equals(halves[1])


def test_bids_are_the_batchs_bid_columns():
    ns = np.arange(50_000_000, 50_004_096, dtype=np.int64)
    batch = gen.gen_batch(ns, gen.event_times(ns, 0, 1e5), 3)
    is_bid, auction, bidder, price = gen.bids(ns, 3)
    bid = batch.column("bid")
    assert np.array_equal(np.asarray(bid.is_valid()), is_bid)
    flat = bid.filter(bid.is_valid())
    for name, col in (("auction", auction), ("bidder", bidder),
                      ("price", price)):
        assert np.array_equal(np.asarray(flat.field(name)), col), name


def test_seeds_are_mixed_not_xored_in():
    # XORing a small seed into the salts would only swap neighbouring
    # sequence numbers: the multiset of a window's bids would not change
    ns = np.arange(0, 100_000, dtype=np.int64)
    _m, a1, _b, _p = gen.bids(ns, 1)
    _m, a0, _b, _p = gen.bids(ns, 0)
    assert sorted(a1.tolist()) != sorted(a0.tolist())
