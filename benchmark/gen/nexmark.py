"""The benchmark's NEXmark event generator.

The key distribution is NEXmark's own, as Apache Beam's generator has it
(`sdks/java/testing/nexmark`, `GeneratorConfig`, `BidGenerator`,
`AuctionGenerator`, `PersonGenerator`, the defaults of
`NexmarkConfiguration`), which upstream Arroyo's `nexmark` connector
ports: one person, three auctions and 46 bids per 50 events; a bid goes
with probability 1/2 (`hotAuctionRatio` 2) to the hot auction, the first of
the current batch of 100 auction ids, and otherwise to one of the last 100
auctions (`numInFlightAuctions`) or the 10 about to be created; its bidder
is with probability 3/4 (`hotBiddersRatio` 4) the hot bidder, the second of
the current batch of 100 person ids, and otherwise one of the last 1,000
people (`numActivePeople`) or the next 10; an auction's seller likewise
(`hotSellersRatio` 4, the first of the batch); prices are
round(100 x 10^(6u)). So a window's distinct auctions are those created
in it plus ~110, and its hottest auction collects half of the bids made
while 100 auctions were created.

The batch building (flat struct children, counter-based splitmix64
uniforms: the same sequence number gives the same event whatever the
batching) is copied from `arroyo_tpu/connectors/nexmark.py`; the run's
seed is mixed into every salt. That connector draws a cold bid's auction
and bidder from ALL ids so far and moves the hot auction every two ids,
which is not NEXmark's distribution (PERF.md section 7), so the two
generators differ in `_bid_fields` and `_auction_fields` by design. The
fields no benchmark query keys on (names, initial bid, reserve, expiry,
category, channel) are the connector's. Nothing of the program is
imported: no later change to it can move the yardstick.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

PERSON_T = pa.struct(
    [
        ("id", pa.int64()),
        ("name", pa.string()),
        ("email_address", pa.string()),
        ("credit_card", pa.string()),
        ("city", pa.string()),
        ("state", pa.string()),
        ("datetime", pa.timestamp("ns")),
        ("extra", pa.string()),
    ]
)
AUCTION_T = pa.struct(
    [
        ("id", pa.int64()),
        ("item_name", pa.string()),
        ("description", pa.string()),
        ("initial_bid", pa.int64()),
        ("reserve", pa.int64()),
        ("datetime", pa.timestamp("ns")),
        ("expires", pa.timestamp("ns")),
        ("seller", pa.int64()),
        ("category", pa.int64()),
        ("extra", pa.string()),
    ]
)
BID_T = pa.struct(
    [
        ("auction", pa.int64()),
        ("bidder", pa.int64()),
        ("price", pa.int64()),
        ("channel", pa.string()),
        ("url", pa.string()),
        ("datetime", pa.timestamp("ns")),
        ("extra", pa.string()),
    ]
)

FIELDS = [("person", PERSON_T), ("auction", AUCTION_T), ("bid", BID_T)]
SCHEMA = pa.schema(
    [pa.field(n, t) for n, t in FIELDS]
    + [pa.field("_timestamp", pa.timestamp("ns"), nullable=False)]
)

# canonical proportions per 50-event epoch
PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
PROPORTION_DENOMINATOR = 50
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10
NUM_CATEGORIES = 5
# NexmarkConfiguration's defaults: 1 - 1/ratio of the draws are hot
HOT_AUCTION_RATIO = 2
HOT_SELLER_RATIO = 4
HOT_BIDDER_RATIO = 4
NUM_IN_FLIGHT_AUCTIONS = 100
NUM_ACTIVE_PEOPLE = 1000
# the generators' constants: a hot id holds for a batch of this many ids,
# and a cold draw may run this many ids ahead of the newest
HOT_BATCH = 100
AUCTION_ID_LEAD = 10
PERSON_ID_LEAD = 10

_STATES = ["AZ", "CA", "ID", "OR", "WA", "WY"]
_CITIES = ["Phoenix", "Los Angeles", "San Francisco", "Boise", "Portland",
           "Bend", "Redmond", "Seattle", "Kent", "Cheyenne"]
_FIRST = ["Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate", "Julie",
          "Sarah", "Deiter", "Walter"]
_LAST = ["Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton",
         "Smith", "Jones", "Noris"]
_CHANNELS = ["Google", "Facebook", "Baidu", "Apple"]


def splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(0xFFFFFFFFFFFFFFFF)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x


def seed_key(seed: int) -> np.uint64:
    """The 64-bit word XORed into every salt: the seed mixed first,
    because XORing a small seed in directly would only swap neighbouring
    sequence numbers and leave every window's counts as they were."""
    s = np.asarray([seed, 0], dtype=np.uint64)
    k = splitmix64(s)
    return k[0] ^ k[1]


def _u01_multi(ns, salts, key) -> np.ndarray:
    """Counter-based uniforms in [0, 1): splitmix64(n ^ salt ^ key), one
    row per salt. The same n gives the same event whatever the batching."""
    arr = np.asarray(ns, dtype=np.uint64)
    s = (np.asarray(salts, dtype=np.uint64) ^ key)[:, None]
    h = splitmix64(arr[None, :] ^ s)
    return h.astype(np.float64) / float(1 << 64)


def _person_fields(ns, key):
    """Vectorized person field generation (counter-based, deterministic)."""
    ns = np.asarray(ns, dtype=np.int64)
    u = _u01_multi(ns, (0xE1, 0xE2, 0xE3, 0xE4, 0xE5, 0xE6, 0xE7, 0xE8), key)
    first = (u[0] * len(_FIRST)).astype(np.int64)
    last = (u[1] * len(_LAST)).astype(np.int64)
    city = (u[2] * len(_CITIES)).astype(np.int64)
    state = (u[3] * len(_STATES)).astype(np.int64)
    cc = [(u[4 + j] * 10000).astype(np.int64) for j in range(4)]
    return first, last, city, state, cc


def _person_names(first, last) -> list:
    """"<first> <last>" per person, from `_person_fields`' two draws."""
    return [f"{_FIRST[f]} {_LAST[l]}"
            for f, l in zip(first.tolist(), last.tolist())]


def _active_person(last_person0, u):
    """`PersonGenerator.nextBase0PersonId`: one of the last
    NUM_ACTIVE_PEOPLE people, or of the PERSON_ID_LEAD not yet created."""
    n_people = last_person0 + 1
    active = np.minimum(n_people, NUM_ACTIVE_PEOPLE)
    return n_people - active + (u * (active + PERSON_ID_LEAD)).astype(np.int64)


def _auction_fields(ns, key):
    """Vectorized auction field generation."""
    ns = np.asarray(ns, dtype=np.int64)
    last_person0 = ns // PROPORTION_DENOMINATOR     # base 0, as Beam counts
    u = _u01_multi(ns, (0xF1, 0xF2, 0xF3, 0xF4, 0xF5, 0xF6), key)
    hot = u[0] < (HOT_SELLER_RATIO - 1) / HOT_SELLER_RATIO
    seller = FIRST_PERSON_ID + np.where(
        hot, last_person0 // HOT_BATCH * HOT_BATCH,
        _active_person(last_person0, u[1]))
    initial = 1 + (u[2] * 100).astype(np.int64)
    reserve = initial + (u[3] * 100).astype(np.int64)
    expires_s = 1 + (u[4] * 9).astype(np.int64)
    category = FIRST_CATEGORY_ID + (u[5] * NUM_CATEGORIES).astype(np.int64)
    return seller, initial, reserve, expires_s, category


@lru_cache(maxsize=8)
def _empty_str_col(n: int) -> "pa.Array":
    """Constant '' column of length n (the structs' `extra` field),
    cached per batch-size: arrow arrays are immutable, and building an
    8k-element python list three times per batch showed in the profile."""
    return pa.array([""] * n, type=pa.string())


def _last_auction_ids(ns: np.ndarray) -> np.ndarray:
    """Vectorized inclusive last-auction-id per sequence number — the ONE
    definition of the formula (scalar last_auction_id and both generation
    paths derive from it, keeping them bit-identical)."""
    ns = np.asarray(ns, dtype=np.int64)
    epoch, offset = np.divmod(ns, PROPORTION_DENOMINATOR)
    done = np.clip(offset - PERSON_PROPORTION + 1, 0, AUCTION_PROPORTION)
    return FIRST_AUCTION_ID + epoch * AUCTION_PROPORTION + done - 1


def _bid_fields(ns, key):
    """Vectorized bid field generation shared by event() and gen_batch()."""
    ns = np.asarray(ns, dtype=np.int64)
    last_auction0 = _last_auction_ids(ns) - FIRST_AUCTION_ID
    last_person0 = ns // PROPORTION_DENOMINATOR
    u = _u01_multi(ns, (0xA1, 0xA2, 0xB1, 0xB2, 0xC1, 0xD1), key)
    # `BidGenerator.nextBid`: the first auction of the batch of HOT_BATCH,
    # or `AuctionGenerator.nextBase0AuctionId`: one still in flight or
    # about to be created
    hot = u[0] < (HOT_AUCTION_RATIO - 1) / HOT_AUCTION_RATIO
    lo = np.maximum(last_auction0 - NUM_IN_FLIGHT_AUCTIONS, 0)
    cold = lo + (
        u[1] * (last_auction0 - lo + 1 + AUCTION_ID_LEAD)).astype(np.int64)
    auction = FIRST_AUCTION_ID + np.where(
        hot, last_auction0 // HOT_BATCH * HOT_BATCH, cold)
    # the second person of the batch, so that hot bidders and hot sellers
    # do not collide
    hot_b = u[2] < (HOT_BIDDER_RATIO - 1) / HOT_BIDDER_RATIO
    bidder = FIRST_PERSON_ID + np.where(
        hot_b, last_person0 // HOT_BATCH * HOT_BATCH + 1,
        _active_person(last_person0, u[3]))
    # `PriceGenerator.nextPrice`: round(10^(6u) x 100)
    price = np.rint(100.0 * 10.0 ** (u[4] * 6.0)).astype(np.int64)
    channel = (u[5] * len(_CHANNELS)).astype(np.int64)
    return auction, bidder, price, channel


def gen_batch(ns: np.ndarray, ts: np.ndarray, seed: int = 0) -> "pa.RecordBatch":
    """Vectorized batch generation for a range of sequence numbers: all
    three event kinds build their struct children as flat arrays with
    validity masks (no python dict per row); strings ride arrow C
    kernels. Deterministic in the sequence-number range and bit-identical
    to the scalar event() path (pinned by
    test_nexmark_gen_batch_matches_scalar_generator). Used by the source
    hot loop and benchmarks."""
    key = seed_key(seed)
    offs = ns % PROPORTION_DENOMINATOR
    is_bid = offs >= PERSON_PROPORTION + AUCTION_PROPORTION
    is_person = offs < PERSON_PROPORTION
    n = len(ns)

    def _scat_i(idx, vals):
        out = np.zeros(n, dtype=np.int64)
        out[idx] = vals
        return out

    def _expand(small: "pa.StructArray", idx: np.ndarray) -> "pa.Array":
        """Expand a subset-size struct to full batch width with one take:
        null indices become null rows — replaces per-field full-width
        scatters (persons/auctions are ~4% of events but paid full-n
        object-array scatters per string field)."""
        pos = np.zeros(n, dtype=np.int64)
        pos[idx] = np.arange(len(idx))
        keep = np.zeros(n, dtype=bool)
        keep[idx] = True
        return small.take(pa.array(pos, mask=~keep))

    # persons/auctions share the vectorized field helpers with event()
    # (bit-identical); struct children are built at SUBSET size and
    # expanded to batch width by one take with null indices
    pi = np.nonzero(is_person)[0]
    person_arr = pa.nulls(n, type=PERSON_T)
    if len(pi):
        pns = ns[pi]
        first, last, city, state, cc = _person_fields(pns, key)
        ids = FIRST_PERSON_ID + pns // PROPORTION_DENOMINATOR
        names = _person_names(first, last)
        emails = [
            f"{nm.replace(' ', '.').lower()}@example.com" for nm in names
        ]
        ccs = [
            f"{a:04d} {b:04d} {c:04d} {d:04d}"
            for a, b, c, d in zip(*(x.tolist() for x in cc))
        ]
        person_arr = _expand(
            pa.StructArray.from_arrays(
                [
                    pa.array(ids),
                    pa.array(names, type=pa.string()),
                    pa.array(emails, type=pa.string()),
                    pa.array(ccs, type=pa.string()),
                    pa.array([_CITIES[i] for i in city.tolist()],
                             type=pa.string()),
                    pa.array([_STATES[i] for i in state.tolist()],
                             type=pa.string()),
                    pa.array(ts[pi]).cast(pa.timestamp("ns")),
                    _empty_str_col(len(pi)),
                ],
                fields=list(PERSON_T),
            ),
            pi,
        )
    ai = np.nonzero(~is_bid & ~is_person)[0]
    auction_arr = pa.nulls(n, type=AUCTION_T)
    if len(ai):
        ans = ns[ai]
        seller, initial, reserve, expires_s, category = _auction_fields(ans, key)
        aids = _last_auction_ids(ans)
        aid_list = aids.tolist()
        auction_arr = _expand(
            pa.StructArray.from_arrays(
                [
                    pa.array(aids),
                    pa.array([f"item-{a}" for a in aid_list],
                             type=pa.string()),
                    pa.array(
                        [f"description of item {a}" for a in aid_list],
                        type=pa.string(),
                    ),
                    pa.array(initial),
                    pa.array(reserve),
                    pa.array(ts[ai]).cast(pa.timestamp("ns")),
                    pa.array(ts[ai] + expires_s * 1_000_000_000).cast(
                        pa.timestamp("ns")),
                    pa.array(seller),
                    pa.array(category),
                    _empty_str_col(len(ai)),
                ],
                fields=list(AUCTION_T),
            ),
            ai,
        )
    bi = np.nonzero(is_bid)[0]
    bid_arr = pa.nulls(n, type=BID_T)
    if len(bi):
        # vectorized struct construction: children built as flat arrays with
        # a validity mask (no python dict per bid)
        auction, bidder, price, channel = _bid_fields(ns[bi], key)
        valid = np.zeros(n, dtype=bool)
        valid[bi] = True

        def scatter(vals):
            return _scat_i(bi, vals)

        # url/channel built in arrow C kernels (int->string cast + concat,
        # dictionary take): ~46% of events are bids, and a python f-string
        # per bid dominated the generator's profile
        urls = pc.binary_join_element_wise(
            pa.scalar("https://auction.example.com/item/"),
            pc.cast(pa.array(scatter(auction)), pa.string()),
            "",
        )
        chans = pc.take(
            pa.array(_CHANNELS, type=pa.string()),
            pa.array(scatter(channel)),
        )
        mask = pa.array(~valid)
        bid_arr = pa.StructArray.from_arrays(
            [
                pa.array(scatter(auction)),
                pa.array(scatter(bidder)),
                pa.array(scatter(price)),
                chans,
                urls,
                pa.array(np.where(valid, ts, 0)).cast(pa.timestamp("ns")),
                _empty_str_col(n),
            ],
            fields=list(BID_T),
            mask=mask,
        )
    return pa.RecordBatch.from_arrays(
        [
            person_arr,
            auction_arr,
            bid_arr,
            pa.array(ts, type=pa.int64()).cast(pa.timestamp("ns")),
        ],
        schema=SCHEMA,
    )


def event_times(ns: np.ndarray, origin_ns: int, rate: float) -> np.ndarray:
    """Schedule-based event time: event n happened at origin + n / rate,
    whether or not it was delivered on time (the open-loop rule)."""
    return origin_ns + np.round(
        np.asarray(ns, dtype=np.int64) * (1e9 / rate)).astype(np.int64)


def bids(ns: np.ndarray, seed: int):
    """(mask of bid events, auction, bidder, price of those) for the
    sequence numbers `ns`: what a plain reference needs of the stream."""
    ns = np.asarray(ns, dtype=np.int64)
    is_bid = kinds(ns)["bid"]
    auction, bidder, price, _channel = _bid_fields(ns[is_bid], seed_key(seed))
    return is_bid, auction, bidder, price


def kinds(ns: np.ndarray) -> dict:
    """{"person" | "auction" | "bid": mask of that kind's events} among the
    sequence numbers `ns`."""
    offs = np.asarray(ns, dtype=np.int64) % PROPORTION_DENOMINATOR
    is_person = offs < PERSON_PROPORTION
    is_bid = offs >= PERSON_PROPORTION + AUCTION_PROPORTION
    return {"person": is_person, "auction": ~is_bid & ~is_person,
            "bid": is_bid}


def events(ns: np.ndarray, ts: np.ndarray, seed: int,
           reads=("person", "auction", "bid")) -> dict:
    """{kind: {"ts": event times, field: values}} of the events of each
    kind in `reads` among the sequence numbers `ns` with event times `ts`:
    what a plain reference over more than the bids needs of the stream, as
    `gen_batch` sends it. A person's id, name, city and state (q3, q8); an
    auction's id, seller, category, initial bid, reserve and expiry (q4,
    q6, q8, q9); a bid's auction, bidder and price. Every draw is
    `_person_fields`', `_auction_fields`' or `_bid_fields`', the ones
    `gen_batch` makes; strings come as object arrays."""
    ns = np.asarray(ns, dtype=np.int64)
    ts = np.asarray(ts, dtype=np.int64)
    key = seed_key(seed)
    masks = kinds(ns)
    out = {}
    for kind in reads:
        m = masks[kind]
        kns, kts = ns[m], ts[m]
        if kind == "person":
            first, last, city, state, _cc = _person_fields(kns, key)
            fields = {
                "id": FIRST_PERSON_ID + kns // PROPORTION_DENOMINATOR,
                "name": np.asarray(_person_names(first, last), dtype=object),
                "city": np.asarray(_CITIES, dtype=object)[city],
                "state": np.asarray(_STATES, dtype=object)[state]}
        elif kind == "auction":
            seller, initial, reserve, expires_s, category = _auction_fields(
                kns, key)
            fields = {
                "id": _last_auction_ids(kns), "seller": seller,
                "category": category, "initial_bid": initial,
                "reserve": reserve,
                "expires": kts + expires_s * 1_000_000_000}
        else:
            auction, bidder, price, _channel = _bid_fields(kns, key)
            fields = {"auction": auction, "bidder": bidder, "price": price}
        out[kind] = {"ts": kts, **fields}
    return out
