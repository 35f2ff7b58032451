"""The timed window is exactly `--seconds` long, whatever the engine is
busy with at the deadline: the source's loop against a stand-in engine
that holds one acceptance across it."""

import asyncio
import time

import pytest

import schedule
from feed import Feed, Traffic

ROWS = 100


class Engine:
    """Takes a batch in 5 ms; the batch offered at `stall_at` seconds into
    the window is held for `stall` seconds (a window close)."""

    def __init__(self, feed, stall_at=None, stall=0.0):
        self.feed, self.stall_at, self.stall = feed, stall_at, stall
        self.taken = []

    async def check_control(self, collector):
        return None

    async def collect(self, batch):
        start = self.feed.t_window_start
        held = (self.stall_at is not None and start is not None
                and time.monotonic() - start >= self.stall_at)
        if held:
            self.stall_at = None
        await asyncio.sleep(self.stall if held else 0.005)
        self.taken.append(batch.num_rows)


def drive(seconds, **engine):
    traffic = Traffic(mode="catchup", nominal_rate=1000.0,
                      warm_event_seconds=0.5, batch_rows=ROWS)
    feed = Feed(traffic, seed=3, seconds=seconds)
    feed.schedule = schedule.Grid(feed, 2_000_000_000)
    ends = []
    feed.on_window_end = lambda: ends.append(time.monotonic())
    eng = Engine(feed, **engine)
    feed.start()
    try:
        asyncio.run(feed.drive(eng, eng))
    finally:
        feed.close()
    return feed, eng, ends


def test_the_window_closes_at_the_deadline_while_the_engine_holds_a_batch():
    feed, eng, ends = drive(0.6, stall_at=0.4, stall=1.0)
    assert len(ends) == 1                       # closed once, by the timer
    assert feed.t_window_end - feed.t_window_start == pytest.approx(
        0.6, abs=0.05)
    # the batch in flight at the deadline is delivered (it is part of the
    # stream that is compared) but is not the window's, and is the last
    assert feed.n_delivered == feed.n_window_end + ROWS
    assert sum(eng.taken) == feed.n_delivered - feed.n_first
    assert feed.n_window_end > feed.n_window_start


def test_without_a_stall_the_loop_and_the_timer_agree():
    feed, _eng, ends = drive(0.3)
    assert len(ends) == 1
    assert feed.t_window_end - feed.t_window_start == pytest.approx(
        0.3, abs=0.05)
    assert 0 <= feed.n_delivered - feed.n_window_end <= ROWS


# Set-up's own settings, and the stall they are for (a first compile in a
# checkout blocks the event loop for longer than the program's liveness
# limits): the window waits until checkpoints flow again.


class Compiling(Engine):
    """Blocks the event loop (not a coroutine's wait: the loop itself) for
    `block` seconds on the warm-up's third batch, as a compile does."""

    def __init__(self, feed, block):
        super().__init__(feed)
        self.block = block

    async def collect(self, batch):
        if len(self.taken) == 2:
            time.sleep(self.block)
        await super().collect(batch)


def drive_through_a_compile(long_stall_s, publish_after):
    traffic = Traffic(mode="catchup", nominal_rate=1000.0,
                      warm_event_seconds=0.5, batch_rows=ROWS)
    feed = Feed(traffic, seed=3, seconds=0.2)
    feed.schedule = schedule.Grid(feed, 2_000_000_000)
    feed.long_stall_s = long_stall_s
    published = []

    def checkpoint():              # one barrier through, and published
        feed.barriers.append((7, time.time_ns()))
        published.append(time.monotonic())

    feed.published_epoch = lambda: 7 if published else 0
    eng = Compiling(feed, block=0.4)
    feed.start()

    async def job():
        asyncio.get_event_loop().call_later(publish_after, checkpoint)
        await feed.drive(eng, eng)

    try:
        asyncio.run(job())
    finally:
        feed.close()
    return feed, published


def test_after_a_long_stall_the_window_waits_for_a_checkpoint_begun_after_it():
    feed, published = drive_through_a_compile(0.2, publish_after=1.0)
    assert feed.longest_stall_s == pytest.approx(0.4, abs=0.1)
    assert feed.long_stall_end_ns is not None
    assert feed.t_window_start >= published[0]
    assert not feed.gate_timed_out


def test_a_stall_shorter_than_the_configurations_limit_holds_nothing_up():
    feed, published = drive_through_a_compile(15.0, publish_after=1.0)
    assert feed.long_stall_end_ns is None
    assert not published or feed.t_window_start < published[0]


def test_a_checkpoint_begun_before_the_stall_does_not_count():
    traffic = Traffic(mode="catchup", nominal_rate=1000.0,
                      warm_event_seconds=0.5, batch_rows=ROWS)
    feed = Feed(traffic, seed=3, seconds=0.2)
    feed.barriers = [(3, 1_000), (4, 3_000)]
    feed.long_stall_end_ns = 2_000
    feed.published_epoch = lambda: 3
    assert not feed._settled()           # epoch 4 is the first after it
    feed.published_epoch = lambda: 4
    assert feed._settled()


def test_set_ups_settings_end_where_the_window_starts():
    import types

    import run as run_mod

    settings = {"pipeline": {"source_batch_size": 8192}}
    setup = {"controller": {"heartbeat_timeout": 600},
             "pipeline": {"checkpointing": {"interval": 3}}}
    both = run_mod.merged(settings, setup)
    assert both == {"pipeline": {"source_batch_size": 8192,
                                 "checkpointing": {"interval": 3}},
                    "controller": {"heartbeat_timeout": 600}}
    assert settings == {"pipeline": {"source_batch_size": 8192}}

    def cfg(timeout, interval):
        return types.SimpleNamespace(
            controller=types.SimpleNamespace(heartbeat_timeout=timeout,
                                             other=1),
            pipeline=types.SimpleNamespace(
                source_batch_size=8192,
                checkpointing=types.SimpleNamespace(interval=interval)))

    live, before = cfg(600, 3), cfg(30.0, 10)
    live.controller.other = 2            # not set-up's: stays
    run_mod.put_back(live, before, setup)
    assert live.controller.heartbeat_timeout == 30.0
    assert live.pipeline.checkpointing.interval == 10
    assert live.controller.other == 2
