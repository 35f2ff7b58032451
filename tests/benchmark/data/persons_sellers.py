"""A stub reference over NEXmark's OTHER two streams, for the tests of the
comparison alone (`test_bench_whole_stream.py`): the persons created in a
10 s tumbling window who also sold an auction in it, (id, name). It is
NEXmark q8's shape in the text `chip_smoke.py` carries, kept beside the
tests and not under `benchmark/reference/`: the `nexmark-q8` configuration
is a `model_config` issue's to bring (PERF.md section 7).

It declares `READS`, so the comparison hands it one argument: {kind:
{"ts": event times, field: values}} of persons and auctions, and no bid.
"""

import numpy as np

READS = ("person", "auction")
SIZE_NS = 10_000_000_000
SLIDE_NS = 10_000_000_000
COLUMNS = ("id", "name")


def _window(stream, end):
    p, a = stream["person"], stream["auction"]
    plo, phi = np.searchsorted(p["ts"], [end - SIZE_NS, end], side="left")
    alo, ahi = np.searchsorted(a["ts"], [end - SIZE_NS, end], side="left")
    return (p["id"][plo:phi], p["name"][plo:phi],
            np.unique(a["seller"][alo:ahi]), ahi - alo)


def compute(stream, ends):
    """{window end: sorted rows (id, name)} for each end in `ends`."""
    out = {}
    for end in ends:
        ids, names, sellers, _n = _window(stream, end)
        sold = np.isin(ids, sellers)
        out[int(end)] = sorted(zip(ids[sold].tolist(), names[sold].tolist()))
    return out


def flows(stream, ends):
    """[(what, rows in, rows out)] of the two aggregates in front of the
    join: the persons per (id, name) and window, the auctions per seller
    and window."""
    persons = auctions = sellers = 0
    for end in ends:
        ids, _names, sold, n = _window(stream, end)
        persons += len(ids)
        auctions += n
        sellers += len(sold)
    return [("persons per id, name and window",
             len(stream["person"]["ts"]), persons),
            ("auctions per seller and window",
             len(stream["auction"]["ts"]), sellers)]
