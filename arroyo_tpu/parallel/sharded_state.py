"""Mesh-sharded window state: the multi-chip execution path.

The reference scales keyed aggregation by running parallel subtasks wired
with a TCP shuffle (/root/reference/crates/arroyo-worker/src/
network_manager.rs; engine.rs:209-365 is the subtask wiring). The
TPU-native equivalent keeps ALL key shards' accumulator state resident on
a device mesh — window/join accumulator state never leaves HBM between
micro-batches — and replaces the network shuffle with an exchange tier
chosen per deployment (`tpu.mesh_exchange`, default `auto`):

    host: rows -> global slots   [MeshSlotDirectory: hash keys to an
                                  owning shard (splitmix64, the same
                                  routing contract as the host shuffle
                                  and `device_owners_for` below);
                                  per-shard directories assign locals]

  * `device` — the GSPMD device-resident keyed exchange (real chip
    meshes). ONE fused route+scatter+reduce jitted program takes the
    src-major packed buffer ([S, C]: rows chopped positionally across
    source shards, `NamedSharding` over the 1-D "keys" mesh), derives
    each row's owner shard from its global slot ON DEVICE, positions
    rows into the [S, R] all_to_all cells with a one-hot rank cumsum,
    exchanges them over ICI (`jax.lax.all_to_all` — the collective XLA
    compiles into the step), and scatter-reduces into the local state
    shard. Duplicate slots reduce IN the scatter, so the steady-state
    path has NO host combiner: host work per flush is a concatenate,
    a pad and a bincount (cell-rung sizing).

  * `host_fed` — the fallback exchange (multi-process meshes without
    ICI collectives, and single-process VIRTUAL meshes — see below).
    Rows are pre-reduced by the host combiner (one row per touched
    slot per flush) and hash-routed at packing time into a dst-major
    [S, R] buffer: the sharded host->device transfer IS the shuffle
    and the step has no collective at all.

    emission: jitted (shard, slot) gather -> host, once per watermark
    wave, chunked at `tpu.mesh_emission_chunk` and padded on the
    sticky emission rung ladder (see _StickyRung).

Why `auto` resolves to `host_fed` on a virtual mesh: under
`--xla_force_host_platform_device_count=N` every "device" is the same
host CPU — the all_to_all is a memcpy between buffers of one process,
XLA-CPU scatters execute serially, and S shards' route work shares one
core, so on-device routing costs strictly more than routing in the
packing pass while buying zero parallelism. On a real chip mesh the
same routing is S-way parallel and the collective rides ICI, which is
where the `device` tier wins (and why it is the default there).

Shape discipline (the round-11 ledger's lesson — 52 XLA compiles cost
1.7s of a 2.4s mesh run): jitted programs are cached PROCESS-WIDE and
shared by every accumulator with the same physical layout (the two
identical hop-count stages of nexmark q5 trace one program set, not
two), and all padding rungs are chosen by sticky hysteresis ladders
(_StickyRung) so steady state locks onto one shape per program instead
of re-specializing on every flush's row-count wander.

This is an *engine execution mode*, not a demo: window operators
construct this pair when `tpu.mesh_devices >= 2` (operators/windows.py)
and run their normal assign/update/gather/checkpoint protocol against
it — global slots encode (shard, local slot) so every Accumulator API
carries over, and checkpoint capture (snapshot/gather) flushes pending
micro-batched rows before reading, which keeps chaos drills
byte-identical across exchange tiers.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs import device as obs_device
from ..obs import timeline
from ..ops.aggregates import (
    Accumulator,
    AggSpec,
    _bucket,
    _neutral,
)
from ..ops._jax import get_jax
from ..ops.directory import SlotDirectory
from ..ops.native import NativeSlotDirectory
from ..types import hash_arrays, hash_column, server_for_hash_array

# global slot encoding: slot = shard * STRIDE + local. The stride is fixed
# (not the current capacity) so capacity growth never re-numbers live slots.
STRIDE = 1 << 32

# process-wide packed-exchange traffic diagnostics (host-fed [S, R] or
# device-routed [S, C] layout, whichever each update used), aggregated
# across every ShardedAccumulator instance; bench --mesh reads these to
# report the padding overhead of the host->device/ICI row shipment and
# the dispatch amortization (device steps per engine update call).
# flushes_elided counts state reads that skipped the pre-read flush
# because no pending update row touched the slots being read.
# rows_busiest: of rows_sent, the rows whose owner was their step's
# busiest destination shard (rows_sent / n_shards when keys spread evenly).
MESH_STATS = {"rows_sent": 0, "rows_padded": 0,
              "dispatches": 0, "updates": 0, "flushes_elided": 0,
              "rows_combined": 0, "rows_busiest": 0}


class MeshSlotDirectory:
    """SlotDirectory facade over per-shard directories: keys hash to an
    owning shard (same splitmix64 hashing as the host shuffle), the shard's
    directory assigns a local slot, and callers see global slots.

    Per-shard directories are python SlotDirectories until
    `swap_to_native` (ops/directory.py `make_directory` calls it, before
    the first key, for keys that flatten to int64 words) — round-5 mesh
    profile showed the python per-shard assigns + tuple-per-key emission
    as the largest host cost on the mesh path. Session windows keep
    python shards (imperative alloc_slot/free lists live there).
    `key_encoding` is the shards'; `take_bin_arrays` and
    `bin_entries_multi` are for "words" shards only."""

    def __init__(self, n_shards: int):
        self.n_shards = n_shards
        self.dirs = [SlotDirectory() for _ in range(n_shards)]
        self._native = False
        self.key_encoding = SlotDirectory.key_encoding

    def swap_to_native(self, native_mod, n_keys: int) -> bool:
        """Replace the per-shard python directories with C++ tables
        (callable only while empty). Returns True on swap."""
        if native_mod is None or any(d.n_live for d in self.dirs):
            return False
        self.dirs = [
            NativeSlotDirectory(native_mod, n_keys=n_keys)
            for _ in range(self.n_shards)
        ]
        self._native = True
        self.key_encoding = NativeSlotDirectory.key_encoding
        return True

    @property
    def n_live(self) -> int:
        return sum(d.n_live for d in self.dirs)

    @property
    def by_bin(self):
        # truthiness/membership probe ("anything live?", "which bins?") —
        # values are True like the native directory, not per-key maps, so
        # the per-watermark check stays O(bins) not O(keys)
        return {b: True for d in self.dirs for b in d.by_bin}

    def required_capacity(self) -> int:
        """Per-shard capacity needed (max across shards, + scratch)."""
        return max(d.required_capacity() for d in self.dirs)

    def owners_for(self, key_cols: List[np.ndarray], n_rows: int) -> np.ndarray:
        if not key_cols:
            return np.zeros(n_rows, dtype=np.int64)
        return server_for_hash_array(
            hash_arrays([hash_column(c) for c in key_cols]), self.n_shards
        )

    def assign(
        self, bins: np.ndarray, key_cols: List[np.ndarray]
    ) -> np.ndarray:
        n = len(bins)
        if n == 0:
            return np.empty(0, dtype=np.int64)
        owners = self.owners_for(key_cols, n)
        out = np.empty(n, dtype=np.int64)
        for shard in range(self.n_shards):
            sel = np.nonzero(owners == shard)[0]
            if len(sel) == 0:
                continue
            local = self.dirs[shard].assign(
                bins[sel], [c[sel] for c in key_cols]
            )
            out[sel] = shard * STRIDE + local
        return out

    def bins_up_to(self, bin_exclusive: int) -> List[int]:
        bins = set()
        for d in self.dirs:
            bins.update(b for b in d.by_bin if b < bin_exclusive)
        return sorted(bins)

    def live_bins(self) -> List[int]:
        bins = set()
        for d in self.dirs:
            bins.update(d.by_bin)
        return sorted(bins)

    def peek_bin(self, b: int) -> Optional[dict]:
        out = {}
        for shard, d in enumerate(self.dirs):
            m = d.peek_bin(b)
            if m:
                for key, slot in m.items():
                    out[key] = shard * STRIDE + slot
        return out or None

    def bin_entries(self, b: int):
        if self._native:
            # native shards return int64 key MATRICES — concatenating
            # them keeps the emission path vectorized end to end (the
            # sliding merge branches on ndarray keys)
            mats: List[np.ndarray] = []
            slot_chunks = []
            for shard, d in enumerate(self.dirs):
                kmat, s = d.bin_entries(b)
                if len(s):
                    mats.append(kmat)
                    slot_chunks.append(s + shard * STRIDE)
            if not slot_chunks:
                return (np.empty((0, self.dirs[0]._stride), dtype=np.int64),
                        np.empty(0, dtype=np.int64))
            return np.concatenate(mats), np.concatenate(slot_chunks)
        keys: List[tuple] = []
        slot_chunks = []
        for shard, d in enumerate(self.dirs):
            k, s = d.bin_entries(b)
            keys.extend(k)
            slot_chunks.append(s + shard * STRIDE)
        return keys, (
            np.concatenate(slot_chunks)
            if slot_chunks
            else np.empty(0, dtype=np.int64)
        )

    def take_bin(self, b: int) -> Tuple[List[tuple], np.ndarray]:
        keys: List[tuple] = []
        slot_chunks: List[np.ndarray] = []
        for shard, d in enumerate(self.dirs):
            k, s = d.take_bin(b)
            keys.extend(k)
            slot_chunks.append(s + shard * STRIDE)
        return keys, (
            np.concatenate(slot_chunks)
            if slot_chunks
            else np.empty(0, dtype=np.int64)
        )

    def take_bin_arrays(self, b: int):
        """Vectorized take (native shards only). One C call per shard;
        outputs fill preallocated buffers."""
        per_shard: List[tuple] = []  # (shard, key cols, local slots)
        total = 0
        for shard, d in enumerate(self.dirs):
            cols, s = d.take_bin_arrays(b)
            if len(s):
                per_shard.append((shard, cols, s))
                total += len(s)
        stride = self.dirs[0]._stride
        if not per_shard:
            z = np.empty(0, dtype=np.int64)
            return [z for _ in range(stride)], z
        out_cols = [np.empty(total, dtype=np.int64) for _ in range(stride)]
        out_slots = np.empty(total, dtype=np.int64)
        off = 0
        for shard, cols, s in per_shard:
            n = len(s)
            for j, c in enumerate(cols):
                out_cols[j][off:off + n] = c
            np.add(s, shard * STRIDE, out=out_slots[off:off + n])
            off += n
        return out_cols, out_slots

    def bin_entries_multi(self, bins) -> Tuple[np.ndarray, np.ndarray]:
        """Concatenated (key matrix, global slots) over SEVERAL bins in
        one native C call per shard (the sliding merge reads width/slide
        bins per emission; per-bin calls cost S x k crossings). Native
        shards only."""
        bins_arr = np.ascontiguousarray(np.asarray(bins, dtype=np.int64))
        mats: List[np.ndarray] = []
        slot_chunks: List[np.ndarray] = []
        for shard, d in enumerate(self.dirs):
            kmat, s = d.bin_entries_multi(bins_arr)
            if len(s):
                mats.append(kmat)
                slot_chunks.append(s + shard * STRIDE)
        if not slot_chunks:
            return (np.empty((0, self.dirs[0]._stride), dtype=np.int64),
                    np.empty(0, dtype=np.int64))
        return np.concatenate(mats), np.concatenate(slot_chunks)

    def items(self):
        for shard, d in enumerate(self.dirs):
            base = shard * STRIDE
            if self._native:
                # one C call per shard; tuple building and iteration
                # stay in C-level passes (_rows_to_tuples + zip)
                bins, kmat, slots = d.entries_arrays()
                yield from zip(bins.tolist(), d._rows_to_tuples(kmat),
                               (slots + base).tolist())
            else:
                for b, key, slot in d.items():
                    yield b, key, base + slot

    def keys_for_slots(self, slots: np.ndarray):
        """(bin, key) per global slot via the shard directories' reverse
        maps (updating-aggregate dirty tracking); dispatched per shard so
        native shards answer in one C call, results scattered back with
        one object-array assignment per shard."""
        slots = np.asarray(slots, dtype=np.int64)
        out = np.empty(len(slots), dtype=object)
        shards = slots // STRIDE
        locs = slots % STRIDE
        for shard in range(self.n_shards):
            idx = np.nonzero(shards == shard)[0]
            if not len(idx):
                continue
            res = self.dirs[shard].keys_for_slots(locs[idx])
            # element-wise object fill (a bare out[idx] = res would let
            # numpy reshape the (bin, key) 2-tuples into a 2-D array)
            tmp = np.empty(len(res), dtype=object)
            tmp[:] = res
            out[idx] = tmp
        return out.tolist()

    def slots_for_keys(self, b: int, keys: List[tuple]) -> Dict[tuple, int]:
        """Point lookups across shards: each key lives on exactly one
        shard, so probe all shards with the full list and merge (native
        shards share ONE key matrix and answer in one C lookup each; the
        merge is a zip over the hit indices, no per-key method calls)."""
        if not keys:
            return {}
        out: Dict[tuple, int] = {}
        if self._native:
            flat = np.ascontiguousarray(
                self.dirs[0]._keys_to_matrix(keys).reshape(-1)
            )
            for shard, d in enumerate(self.dirs):
                present, slots_raw = d._d.lookup(int(b), flat)
                pres = np.frombuffer(present, dtype=np.uint8)
                hit = np.nonzero(pres)[0]
                if not len(hit):
                    continue
                gslots = np.frombuffer(slots_raw, dtype=np.int64)[hit]
                out.update(zip(
                    (keys[i] for i in hit.tolist()),
                    (gslots + shard * STRIDE).tolist(),
                ))
            return out
        for shard, d in enumerate(self.dirs):
            sub = d.slots_for_keys(b, keys)
            if sub:
                base = shard * STRIDE
                out.update((k, base + int(v)) for k, v in sub.items())
        return out

    def remove(self, b: int, keys: List[tuple]) -> np.ndarray:
        """Remove keys from a bin across shards; each key lives in exactly
        one shard, so per-shard removal of the full list is safe. Native
        shards share one key matrix (built once, one C call per shard).
        Returns freed GLOBAL slots."""
        if not keys:
            return np.empty(0, dtype=np.int64)
        freed = []
        if self._native:
            flat = np.ascontiguousarray(
                self.dirs[0]._keys_to_matrix(keys).reshape(-1)
            )
            for shard, d in enumerate(self.dirs):
                f = np.frombuffer(d._d.remove(int(b), flat), dtype=np.int64)
                if len(f):
                    freed.append(f + shard * STRIDE)
        else:
            for shard, d in enumerate(self.dirs):
                f = d.remove(b, keys)
                if len(f):
                    freed.append(f + shard * STRIDE)
        return (
            np.concatenate(freed) if freed else np.empty(0, dtype=np.int64)
        )

    # -- imperative slot allocation (session windows) -----------------------

    def alloc_slot(self, shard_hint: int) -> int:
        """Allocate one slot on a shard (round-robin hint from the caller);
        session bookkeeping assigns slots imperatively rather than through
        assign(). Python shards only (sessions never swap to native —
        the imperative free lists live in the python directory)."""
        if self._native:
            raise RuntimeError(
                "imperative slot allocation requires python shards"
            )
        d = self.dirs[shard_hint % self.n_shards]
        local = d.free.pop() if d.free else d._alloc()
        return (shard_hint % self.n_shards) * STRIDE + local

    def alloc_slots(self, n: int, shard_hint: int = 0) -> np.ndarray:
        """Vectorized round-robin block allocation: one call allocates n
        slots dealt evenly across shards (the session operator's slot
        pool refill — replaces one Python alloc_slot call per session)."""
        shards = (np.arange(n, dtype=np.int64) + shard_hint) % self.n_shards
        out = np.empty(n, dtype=np.int64)
        for shard in range(self.n_shards):
            idx = np.nonzero(shards == shard)[0]
            if not len(idx):
                continue
            block = self.dirs[shard].alloc_block(len(idx))
            out[idx] = np.asarray(block, dtype=np.int64) + shard * STRIDE
        return out

    def free_slot(self, slot: int):
        self.dirs[int(slot) // STRIDE].free.append(int(slot) % STRIDE)

    def free_slots(self, slots: np.ndarray):
        """Batch free: one list-extend per shard (session expiry waves
        and the session operator's slot-pool return at checkpoint)."""
        slots = np.asarray(slots, dtype=np.int64)
        if not len(slots):
            return
        shards = slots // STRIDE
        locs = slots % STRIDE
        for shard in range(self.n_shards):
            sel = np.nonzero(shards == shard)[0]
            if len(sel):
                self.dirs[shard].free.extend(locs[sel].tolist())


def _pow2_ladder(cap: int, floor: int = 16, fine_from: int = 512) -> tuple:
    """Bucket rungs from `floor` up to and including `cap`: power-of-2 at
    the bottom, then eighth rungs (x1.125 steps) from `fine_from` so the
    large packed buffers — where padded rows actually cost
    host->device/ICI bytes — overshoot by at most 12.5%. Coarser than
    the round-5 sixteenth ladder on purpose: every DISTINCT rung a run
    hits costs a python-side trace + XLA compile per process (~15-45ms
    each — the round-11 ledger's dominant mesh cost), and rung WANDER is
    now absorbed by _StickyRung hysteresis rather than by ladder
    density. Compiled programs persist across processes
    (the compile cache, ops/_jax.py); the python trace does not."""
    rb, b = [], floor
    while b < cap:
        rb.append(b)
        if b >= fine_from:
            num, denom = range(9, 16), 8
        elif b >= max(32, fine_from // 8):
            num, denom = range(5, 8), 4
        else:
            num, denom = (), 1
        rb.extend(x for x in (b * s // denom for s in num) if x < cap)
        b *= 2
    rb.append(cap)
    return tuple(sorted(set(x for x in rb if x <= cap)))


def _arith_ladder(cap: int, quantum: int, floor: int = 16) -> tuple:
    """Emission-side ladder: power-of-2 below `quantum`, then arithmetic
    multiples of `quantum` up to `cap`. Big watermark waves (the
    sliding-merge unions, where padded slots cost real gather work +
    device->host bytes) overshoot by at most `quantum` rows — under 5%
    average for waves a few quanta deep — while the signature count
    stays hard-bounded at cap/quantum + log2(quantum/floor)."""
    rb = []
    b = floor
    while b < quantum:
        rb.append(b)
        b *= 2
    rb.extend(range(quantum, cap + 1, quantum))
    if rb[-1] != cap:
        rb.append(cap)
    return tuple(sorted(set(x for x in rb if x <= cap)))


class _StickyRung:
    """Quantize a stream of buffer sizes onto a ladder with hysteresis.

    A fresh shape signature re-traces and re-compiles its jitted program
    (~15-45ms on CPU-jax, more on a chip) — worth ~20+
    steady-state dispatches — so the rung must not follow every flush's
    row-count wander (the round-11 ledger shows mesh.step_direct
    specializing 14 ways in ONE bench child exactly that way). fit(n)
    reuses the current rung while n fits; on overflow it climbs straight
    to bucket(n); after `decay_after` consecutive fits below half the
    rung it steps down one rung, so a permanently shrunken workload
    stops shipping 2x filler but a single small flush changes nothing."""

    __slots__ = ("ladder", "rung", "_low", "decay_after", "headroom")

    def __init__(self, ladder: tuple, decay_after: int = 8,
                 headroom: float = 1.25):
        self.ladder = ladder
        self.rung = 0
        self._low = 0
        self.decay_after = decay_after
        self.headroom = headroom

    def fit(self, n: int) -> int:
        if n > self.rung:
            # climb with headroom: a ramping workload (window cardinality
            # growing through the run) would otherwise walk EVERY ladder
            # rung on its way up, tracing each once — the exact signature
            # storm the ladder coarsening fights. Successive climbs are
            # geometric in the headroom factor, so a KxX ramp costs
            # ~log(K)/log(headroom*step) climbs; the overshoot decays
            # back one rung at a time once sizes settle.
            self.rung = _bucket(
                n if self.rung == 0 else int(n * self.headroom),
                self.ladder,
            )
            self._low = 0
            return self.rung
        if n <= self.rung // 2:
            self._low += 1
            if self._low >= self.decay_after:
                i = self.ladder.index(self.rung) if self.rung in \
                    self.ladder else 0
                if i > 0:
                    self.rung = self.ladder[i - 1]
                self._low = 0
        else:
            self._low = 0
        return self.rung


# -- device-side owner hashing ------------------------------------------------
#
# The routing contract (PAPER §2.9-2.11): a row's owning shard is
# server_for_hash(splitmix64-combine(per-column splitmix64), n_shards).
# MeshSlotDirectory.owners_for computes it host-side (numpy) at assign
# time; device_owners_for is the jax mirror used ON DEVICE wherever rows
# carry raw key words instead of pre-assigned slots (device-resident
# producers feeding the route step, multi-host shuffles). The two MUST
# agree bit-for-bit — tests/test_parallel.py property-tests them against
# each other across shard counts.

_U64_MASK = 0xFFFFFFFFFFFFFFFF


def _jax_splitmix64(jnp, x):
    """splitmix64 finalizer over uint64 lanes (types._splitmix64)."""
    x = (x + jnp.uint64(0x9E3779B97F4A7C15)) & jnp.uint64(_U64_MASK)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def device_owners_for(key_cols, n_shards: int):
    """Owner shard per row from int64/uint64 key-word columns, computed
    with jax ops (traceable inside jitted route steps). Mirrors
    MeshSlotDirectory.owners_for = server_for_hash_array(hash_arrays(
    [hash_column(c) for c in cols])) exactly: per-column splitmix64,
    seeded xor-mix combine, then the contiguous hash-range map."""
    from ..types import HASH_SEED, _range_size

    from .mesh import _get_jnp

    jnp = _get_jnp()
    if not key_cols:
        return jnp.zeros(0, dtype=jnp.int64)
    cols = [jnp.asarray(c).astype(jnp.uint64) for c in key_cols]
    out = jnp.full(cols[0].shape, jnp.uint64(int(HASH_SEED)),
                   dtype=jnp.uint64)
    for col in cols:
        out = _jax_splitmix64(jnp, out ^ _jax_splitmix64(jnp, col))
    if n_shards == 1:
        return jnp.zeros(cols[0].shape, dtype=jnp.int64)
    owners = (out // jnp.uint64(_range_size(n_shards))).astype(jnp.int64)
    return jnp.minimum(owners, n_shards - 1)


# -- process-wide jitted program cache ----------------------------------------
#
# Jitted mesh programs are pure functions of (mesh, physical layout,
# capacity, mode flags): two accumulators with the same key — e.g. the
# two identical hop-count stages of nexmark q5 — must share ONE traced
# program set, not trace it twice (q5's per-child compile bill halves).
# key_mesh() caches Mesh instances so `id(mesh)` is a stable cache
# component; entries hold the InstrumentedJit wrapper so compile/dispatch
# telemetry is shared too.

_PROGRAMS: Dict[tuple, object] = {}


def _shared_program(key: tuple, build):
    prog = _PROGRAMS.get(key)
    if prog is None:
        prog = _PROGRAMS.setdefault(key, build())
    return prog


def _scatter_body(phys, jnp, neutral=_neutral):
    """Shared per-shard scatter-reduce: applies (flat_slots, valid, vals)
    rows into each physical accumulator row. Rows arrive PRE-REDUCED by
    the host combiner (one row per slot per flush): `valid` carries the
    segment's summed signs (row count for append-only streams, 0 for
    padding), add-source values arrive sign-folded (0 for padding), and
    min/max sources replace padding with the op's neutral."""

    def scatter(state_shards, flat_slots, valid_r, vals_r):
        out = []
        vi = 0
        for (op, dt, src, si), s in zip(phys, state_shards):
            row = s[0]
            if src == "one":
                v = valid_r.astype(row.dtype)
            else:
                v = vals_r[vi]
                vi += 1
                if op != "add":
                    v = jnp.where(valid_r != 0, v, neutral(op, dt))
            if op == "add":
                row = row.at[flat_slots].add(v.astype(row.dtype))
            elif op == "min":
                row = row.at[flat_slots].min(v.astype(row.dtype))
            else:
                row = row.at[flat_slots].max(v.astype(row.dtype))
            out.append(row[None, :])
        return tuple(out)

    return scatter


class SharedMeshSlotDirectory:
    """Slot directory for SALTED mesh aggregation (low-cardinality
    groups, e.g. q5/q7's MAX-per-window stage where every key is the
    window itself): one flat host directory allocates GLOBALLY-unique
    local ids, the nominal owner shard derives as local % S, and the
    salted accumulator spreads each update row across ALL shards at the
    same local index, folding across the shard axis at gather. Without
    this, hash ownership puts every row of a window on one shard — at
    most #windows of S shards ever receive rows (the round-4 mesh
    padding analysis)."""

    def __init__(self, n_shards: int):
        self.n_shards = n_shards
        self._flat = SlotDirectory()
        self.key_encoding = SlotDirectory.key_encoding

    def swap_to_native(self, native_mod, n_keys: int) -> bool:
        """Swap the flat python directory for the C++ table (callable
        only while empty): the salted window-only groupings flatten
        their window struct to int64 words, and the python per-row
        interning + dict assign showed up as the salted stage's largest
        host cost in the mesh profile. Session operators never swap —
        their imperative alloc_slot/free lists live python-side."""
        if native_mod is None or self._flat.n_live:
            return False
        self._flat = NativeSlotDirectory(native_mod, n_keys=n_keys)
        self.key_encoding = NativeSlotDirectory.key_encoding
        return True

    # "words" table only, like the native directory's own
    def take_bin_arrays(self, b: int):
        cols, slots = self._flat.take_bin_arrays(b)
        return cols, self._g(slots)

    def bin_entries_multi(self, bins) -> Tuple[np.ndarray, np.ndarray]:
        kmat, slots = self._flat.bin_entries_multi(bins)
        return kmat, self._g(slots)

    def _g(self, locals_: np.ndarray) -> np.ndarray:
        locals_ = np.asarray(locals_, dtype=np.int64)
        return (locals_ % self.n_shards) * STRIDE + locals_

    def _g1(self, local: int) -> int:
        return (local % self.n_shards) * STRIDE + local

    @property
    def n_live(self) -> int:
        return self._flat.n_live

    @property
    def by_bin(self):
        return {b: True for b in self._flat.by_bin}

    def required_capacity(self) -> int:
        return self._flat.required_capacity()

    def assign(self, bins, key_cols) -> np.ndarray:
        return self._g(self._flat.assign(bins, key_cols))

    def bins_up_to(self, limit):
        return self._flat.bins_up_to(limit)

    def live_bins(self):
        return self._flat.live_bins()

    def peek_bin(self, b):
        m = self._flat.peek_bin(b)
        if not m:
            return None
        return {k: self._g1(s) for k, s in m.items()}

    def bin_entries(self, b):
        keys, slots = self._flat.bin_entries(b)
        return keys, self._g(slots)

    def take_bin(self, b):
        keys, slots = self._flat.take_bin(b)
        return keys, self._g(slots)

    def items(self):
        for b, key, s in self._flat.items():
            yield b, key, self._g1(s)

    def keys_for_slots(self, slots):
        return self._flat.keys_for_slots(
            np.asarray(slots, dtype=np.int64) % STRIDE
        )

    def slots_for_keys(self, b, keys):
        return {k: self._g1(s)
                for k, s in self._flat.slots_for_keys(b, keys).items()}

    def remove(self, b, keys):
        return self._g(self._flat.remove(b, keys))

    def alloc_slot(self, shard_hint: int = 0) -> int:
        return self._g1(self._flat.alloc_slot())

    def alloc_slots(self, n: int, shard_hint: int = 0) -> np.ndarray:
        return self._g(self._flat.alloc_slots(n))

    def free_slot(self, slot: int):
        self._flat.free_slot(int(slot) % STRIDE)

    def free_slots(self, slots: np.ndarray):
        self._flat.free_slots(np.asarray(slots, dtype=np.int64) % STRIDE)


class ShardedAccumulator(Accumulator):
    """Accumulator whose slot arrays live sharded across a 1-D device mesh;
    updates route rows to their owning device with an in-step all_to_all.
    Slots are MeshSlotDirectory global slots (shard * STRIDE + local)."""

    def __init__(
        self,
        specs: List[AggSpec],
        mesh,
        capacity_per_shard: int = 4096,
        rows_per_shard: int = 1024,
        salted: bool = False,
        flush_rows: int = 0,
        exchange: Optional[str] = None,
    ):
        # initialize host-side bookkeeping via the base class with backend
        # 'numpy' (cheap), then replace the state with mesh-sharded arrays
        super().__init__(specs, capacity=capacity_per_shard, backend="numpy")
        from ..config import config as config_fn

        self.backend = "jax-mesh"
        # honor tpu.use_32bit_accumulators exactly like the single-device
        # jax backend (the base ctor only engages it for backend "jax"):
        # halves state bytes, transfer bytes and scatter width on v5e
        self.use32 = bool(
            getattr(config_fn().tpu, "use_32bit_accumulators", False)
        )
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.rows_per_shard = rows_per_shard
        # packing-rung ladders (eighth rungs up top, ≤12.5% overshoot)
        # with sticky hysteresis per layout: steady state locks onto one
        # shape per program instead of re-specializing per flush
        self._rung_direct = _StickyRung(
            _pow2_ladder(rows_per_shard * self.n_shards, floor=16)
        )
        # device-routed exchange rungs: C = src-major rows per source
        # shard, R = all_to_all cell rows (sized from the host bincount)
        self._rung_chunk = _StickyRung(
            _pow2_ladder(max(rows_per_shard, 16), floor=16, fine_from=1 << 30)
        )
        self._rung_cell = _StickyRung(
            _pow2_ladder(max(rows_per_shard, 16), floor=16)
        )
        # multi-host: the mesh may span devices owned by several
        # processes (jax.distributed — parallel/multihost.py). All host
        # buffers then enter the device as GLOBAL arrays (each process
        # materializes only its addressable shards) and every mesh
        # process runs the same steps in lockstep.
        from .multihost import is_multiprocess_mesh

        self._multiproc = is_multiprocess_mesh(mesh)
        # exchange tier: 'device' (fused GSPMD route+scatter+reduce, no
        # host combiner), 'host_fed' (combiner + dst-major [S, R] packed
        # transfer — the multi-process / virtual-mesh fallback). See
        # module docstring for the auto-resolution rationale.
        self._exchange = self._resolve_exchange(exchange)
        # emission/reset/restore reads are chunked at
        # tpu.mesh_emission_chunk and padded on their own sticky ladder:
        # big drain waves re-use the full-chunk program instead of
        # specializing a fresh XLA program per wave size, and steady
        # waves ride one rung with ≤12.5% filler (quarter/eighth rungs
        # from 256) — the round-11 ledger's "emission-rung padding"
        self._emission_chunk = int(
            getattr(config_fn().tpu, "mesh_emission_chunk", 16384) or 16384
        )
        from .mesh import mesh_is_virtual

        if mesh_is_virtual(mesh):
            # virtual mesh: the bottleneck is the per-process python
            # trace each distinct rung costs, not padded bytes (padded
            # slots gather at ~50ns each on the shared host core) — two
            # rungs bound the emission program count at 2 per kind
            self._buckets = (max(self._emission_chunk // 8, 16),
                             self._emission_chunk)
        else:
            # real chip mesh: device->host bytes and XLA compiles both
            # matter; quantum rungs keep steady waves
            # under ~5% padding at a hard-bounded signature count
            self._buckets = _arith_ladder(
                self._emission_chunk, max(self._emission_chunk // 16, 64)
            )
        # owner-sliced emission rung: per-shard slice length (~wave/S)
        self._rung_slice = _StickyRung(_pow2_ladder(1 << 20, floor=16))
        # salted mode (SharedMeshSlotDirectory): update rows spread
        # row-position round-robin across ALL shards at the slot's local
        # index — perfectly balanced regardless of key skew — and gather
        # folds across the shard axis. Requires globally-unique locals
        # and fold-able phys ops (add/min/max; no host-state aggregates).
        self.salted = salted
        # padding diagnostics (VERDICT r3: "document rows-sent vs
        # rows-padded"): rows_sent counts real rows pushed through the
        # packed exchange (either layout); rows_padded counts the
        # neutral filler rows shipped alongside them
        self.rows_sent = 0
        self.rows_padded = 0
        # micro-batching: update() buffers rows host-side and ships one
        # packed exchange + scatter per `flush_rows` rows instead of per
        # engine batch; every state read (gather/reset/restore) that
        # touches a pending slot flushes first, so observers never see
        # stale state — reads of untouched slots keep buffering (the
        # watermark-emission gathers otherwise force a flush per engine
        # batch and pin dispatches/updates near 1). 0 = immediate.
        self.flush_rows = int(flush_rows)
        self._pending: List[tuple] = []   # (slots, vals_list, signs)
        self._pending_rows = 0
        # observed engine-batch row EWMA: the effective flush threshold
        # auto-tunes to >= 4 batches so a configured threshold below the
        # pipeline's natural batch size still coalesces dispatches
        self._ewma_rows = 0
        self._sharding = self._make_sharding()
        self.state = self._fresh_state(capacity_per_shard)

    def _resolve_exchange(self, exchange: Optional[str]) -> str:
        """Pick the exchange tier. Explicit ctor/config choices win; auto
        keeps the host-fed combiner path wherever the device-routed
        exchange cannot pay for itself: multi-process meshes (no ICI
        collectives under the CPU backend) and single-process VIRTUAL
        meshes (every "device" is the same host core — see module
        docstring). Real chip meshes default to the device route."""
        from ..config import config as config_fn
        from .mesh import mesh_is_virtual

        mode = exchange or str(
            getattr(config_fn().tpu, "mesh_exchange", "auto") or "auto"
        )
        if mode not in ("auto", "device", "host_fed"):
            raise ValueError(
                f"tpu.mesh_exchange must be auto|device|host_fed, "
                f"got {mode!r}"
            )
        if mode != "auto":
            return mode
        if self._multiproc or mesh_is_virtual(self.mesh):
            return "host_fed"
        return "device"

    def _program(self, kind: str, build, *extra):
        """Process-wide shared jitted program for this accumulator's
        layout: identical stages (same mesh, phys ops/dtypes, capacity,
        salted/multiproc mode) resolve to ONE traced program set."""
        key = (kind, id(self.mesh), self.capacity, tuple(self.phys),
               self.salted, self._multiproc, self.use32, *extra)
        return _shared_program(key, build)

    def _make_sharding(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self.mesh, P(self.axis, None))

    def _fresh_state(self, capacity: int):
        from jax.sharding import PartitionSpec as P

        from .mesh import _get_jnp
        from .multihost import put_global

        _get_jnp()  # enable x64 before any placement
        return [
            put_global(
                np.full(
                    (self.n_shards, capacity),
                    self._neutral(op, dt),
                    dtype=self._dt(dt),
                ),
                self.mesh,
                P(self.axis, None),
            )
            for op, dt, _, _ in self.phys
        ]

    def _to_dev(self, arr: np.ndarray, shard_dim0: bool):
        """Host buffer -> device array for step/gather inputs: sharded on
        dim 0 over the mesh axis (packed row buffers) or replicated
        (index vectors). Single-process fast path: plain jnp.asarray —
        jit re-shards as needed."""
        from .mesh import _get_jnp

        jnp = _get_jnp()
        if not self._multiproc:
            return jnp.asarray(arr)
        from jax.sharding import PartitionSpec as P

        from .multihost import put_global

        return put_global(arr, self.mesh,
                          P(self.axis) if shard_dim0 else P())

    def _decompose(self, slots: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        return slots // STRIDE, slots % STRIDE

    # -- capacity -----------------------------------------------------------

    def grow(self, min_capacity: int):
        """Grow every shard's local capacity (4x steps). Global slot ids are
        stride-encoded, so no live slot is re-numbered; the old per-shard
        scratch slot is reset to neutral before it becomes allocatable."""
        new_cap = self.capacity
        while new_cap < min_capacity:
            new_cap *= 4
        if new_cap == self.capacity:
            return
        jax = get_jax()

        from .mesh import _get_jnp

        jnp = _get_jnp()
        old_cap = self.capacity
        phys = list(self.phys)
        n_shards = self.n_shards

        # one jitted program for ALL columns, with explicit out_shardings:
        # valid in both single- and multi-process mode (eager concatenate
        # of a global sharded array with a process-local pad is not).
        # grow() is rare (4x capacity steps), so a compile per call is
        # acceptable; a single program per grow beats one per column.
        neutral, dtype = self._neutral, self._dt

        @partial(jax.jit, donate_argnums=(0,), out_shardings=self._sharding)
        def grow_fn(state):
            out = []
            for (op, dt, _, _), x in zip(phys, state):
                pad = jnp.full(
                    (n_shards, new_cap - old_cap), neutral(op, dt),
                    dtype=dtype(dt),
                )
                g = jnp.concatenate([x, pad], axis=1)
                out.append(g.at[:, old_cap - 1].set(neutral(op, dt)))
            return out

        self.state = grow_fn(list(self.state))
        self.capacity = new_cap

    # -- update (hot path) --------------------------------------------------

    def update(
        self,
        slots: np.ndarray,
        cols: Dict[int, np.ndarray],
        signs: Optional[np.ndarray] = None,
    ):
        n = len(slots)
        if n == 0:
            return
        self._check_signed(signs)
        # the leaves of ops/aggregates.py's one-device update, so that one
        # table reads both paths: `agg.pack` is every host step between
        # the operator's scatter and the jitted call (here the batch's
        # part; the flush books the rows), `agg.enqueue` the call
        with timeline.phase("agg.pack"):
            self._update_host(slots, cols, signs)
            if not self.phys:
                return
            MESH_STATS["updates"] += 1
            slots = np.asarray(slots)
            max_local = int((slots % STRIDE).max())
            if max_local >= self.capacity - 1:
                # jit scatters silently drop out-of-bounds updates —
                # callers must grow() first (windows.py _ensure_capacity
                # does); checked at update() time (capacity only ever
                # grows before a deferred flush, so the buffered check
                # stays valid)
                raise ValueError(
                    f"shard accumulator capacity exceeded: local slot "
                    f"{max_local} >= capacity-1={self.capacity - 1}"
                )
            from ..ops.aggregates import _src_values

            vals = [
                np.asarray(_src_values(self.specs[si], src, cols))
                for op, dt, src, si in self.phys if src != "one"
            ]
            self._ewma_rows = (
                n if not self._ewma_rows
                else (self._ewma_rows * 7 + n) // 8
            )
            thr = self._flush_threshold()
            buffered = thr > n or bool(self._pending)
            if buffered:
                self._pending.append(
                    (slots, vals,
                     None if signs is None else np.asarray(signs))
                )
                self._pending_rows += n
        if not buffered:
            self._dispatch_rows(slots, vals, signs)
        elif self._pending_rows >= thr:
            self.flush()

    def _flush_threshold(self) -> int:
        """Effective micro-batch threshold: the configured
        tpu.mesh_flush_rows, auto-raised to ~8 observed engine batches
        (bounded) so a threshold tuned for one workload still coalesces
        dispatches when the pipeline feeds bigger batches — watermark
        waves force a flush anyway, so between waves bigger is cheaper.
        0 disables buffering entirely (immediate dispatch)."""
        if self.flush_rows <= 0:
            return 0
        return max(self.flush_rows, min(8 * self._ewma_rows, 1 << 20))

    def _flush_if_touches(self, slots: np.ndarray):
        """Flush pending update rows only when one could affect `slots`.
        State reads (gather/reset/restore) of slots no pending row
        touches keep buffering — correctness holds because every read
        path comes through here first, and the eventual flush applies
        the buffered scatters in their original order relative to any
        elided read (disjoint slot sets commute)."""
        if not self._pending:
            return
        slots = np.asarray(slots)
        if len(slots):
            # the isin probe sorts both sides — against a large raw
            # pending buffer it costs more than the dispatch it might
            # save, and emission reads of hot bins nearly always overlap
            # pending rows anyway. Probe only when it is cheap AND has a
            # real chance of eliding; otherwise just flush.
            if self._pending_rows * len(slots) > (1 << 22):
                self.flush()
                return
            for p_slots, _, _ in self._pending:
                if np.isin(p_slots, slots, assume_unique=False).any():
                    self.flush()
                    return
        MESH_STATS["flushes_elided"] += 1

    def flush(self):
        """Ship any buffered update rows to the device (one packed
        exchange covering every pending engine batch)."""
        if not self._pending:
            return
        if len(self._pending) == 1:
            slots, vals, signs = self._pending[0]
        else:
            with timeline.phase("agg.pack"):
                slots, vals, signs = self._concat_pending()
        self._pending = []
        self._pending_rows = 0
        self._dispatch_rows(slots, vals, signs)

    def _concat_pending(self):
        slots = np.concatenate([p[0] for p in self._pending])
        vals = [
            np.concatenate([p[1][i] for p in self._pending])
            for i in range(len(self._pending[0][1]))
        ]
        signs = None
        if any(p[2] is not None for p in self._pending):
            signs = np.concatenate([
                p[2] if p[2] is not None
                else np.ones(len(p[0]), dtype=np.int64)
                for p in self._pending
            ])
        return slots, vals, signs

    def _prereduce(self, slots: np.ndarray, vals: List[np.ndarray],
                   signs: Optional[np.ndarray]):
        """Host-side combiner: rows sharing a slot within one flush
        collapse into a single packed row — add sources sum (sign-
        weighted), min/max take their extremum, and the valid word
        carries the segment's summed signs (= row count on append-only
        streams). The packed exchange then ships O(unique slots) rows:
        hot keys no longer skew the per-destination counts that size the
        padded [S, R] buffer (the dominant residual padding source), and
        shipped bytes drop with the dedup ratio. Integer accumulators
        are exact under the reassociation; float sums see the same
        reordering class as XLA's scatter reduction."""
        n = len(slots)
        if n == 0:
            return slots, vals, signs
        # one argsort does all the segmenting work (np.unique would sort
        # a second time and build an inverse nothing needs): sorted-run
        # boundaries give the unique slots and the reduceat bounds
        order = np.argsort(slots, kind="stable")
        s_sorted = slots[order]
        new_seg = np.empty(n, dtype=bool)
        new_seg[0] = True
        np.not_equal(s_sorted[1:], s_sorted[:-1], out=new_seg[1:])
        bounds = np.nonzero(new_seg)[0]
        uniq = s_sorted[bounds]
        MESH_STATS["rows_combined"] += n - len(uniq)
        if len(uniq) == n:
            # no duplicates: only fold signs into add-source values so
            # the kernel's uniform pre-reduced semantics hold
            if signs is not None:
                out_vals = []
                vi = 0
                for op, dt, src, si in self.phys:
                    if src == "one":
                        continue
                    v = vals[vi]
                    vi += 1
                    out_vals.append(
                        v * signs.astype(v.dtype) if op == "add" else v
                    )
                vals = out_vals
            return slots, vals, signs
        sgn = signs[order] if signs is not None else None
        out_vals = []
        vi = 0
        for op, dt, src, si in self.phys:
            if src == "one":
                continue
            v = vals[vi][order]
            vi += 1
            if op == "add":
                if sgn is not None:
                    v = v * sgn.astype(v.dtype)
                out_vals.append(np.add.reduceat(v, bounds))
            elif op == "min":
                out_vals.append(np.minimum.reduceat(v, bounds))
            else:
                out_vals.append(np.maximum.reduceat(v, bounds))
        # per-slot summed signs (plain row count when unsigned): the
        # count word and the padding discriminator. Signed streams only
        # carry add phys (non-invertible aggregates replay host-side),
        # so a zero sum contributes zero everywhere — still correct.
        if sgn is not None:
            counts = np.add.reduceat(sgn, bounds)
        else:
            counts = np.diff(np.append(bounds, n))
        return uniq, out_vals, counts.astype(np.int64, copy=False)

    def _dispatch_rows(self, slots: np.ndarray, vals: List[np.ndarray],
                       signs: Optional[np.ndarray]):
        if self._exchange == "device":
            # GSPMD device-routed exchange: raw rows ship src-major, the
            # fused route+scatter+reduce program owns routing AND the
            # duplicate-slot reduction — no host combiner on this path
            self._dispatch_rows_device(slots, vals, signs)
            return
        with timeline.phase("mesh.combine", n=len(slots)):
            slots, vals, signs = self._prereduce(slots, vals, signs)
        # the layout of every step this flush takes, then the steps
        with timeline.phase("agg.pack"):
            steps = self._layout_steps(slots)
            locals_ = slots % STRIDE
        for program, step, shape, rows, flat, busiest in steps:
            self._note_traffic(len(rows), int(np.prod(shape)), program,
                               shape[-1], busiest)
            self._dispatch(step, shape, rows, flat, locals_, vals, signs)

    def _layout_steps(self, slots: np.ndarray) -> List[tuple]:
        """(program, step, buffer shape, row indices, flat positions, rows
        of the busiest destination) of each step that ships `slots`."""
        n = len(slots)
        S = self.n_shards
        owners = slots // STRIDE
        if self.salted:
            # balanced spread: every shard takes ~n/S rows of each group;
            # the cross-shard fold happens at gather
            owners = np.arange(n, dtype=np.int64) % S
        order = np.argsort(owners, kind="stable")
        so = owners[order]
        starts = np.searchsorted(so, so, side="left")
        pos = np.arange(n, dtype=np.int64) - starts   # rank within owner
        # dst-major [S, R] direct layout: the host already sees every
        # row, so the key shuffle happens at packing time and the
        # sharded host->device transfer IS the routing.
        steps = []
        r_cap = self.rows_per_shard * S
        chunk = pos // r_cap
        for c in range(int(chunk.max()) + 1):
            in_chunk = chunk == c
            pm = pos[in_chunk] - c * r_cap
            dst = so[in_chunk]
            r_c = self._rung_direct.fit(int(pm.max()) + 1)
            steps.append((
                "mesh.step_direct", self._direct_step(), (S, r_c),
                order[in_chunk], dst * r_c + pm,
                int(np.bincount(dst).max()),
            ))
        return steps

    def _dispatch_rows_device(self, slots: np.ndarray,
                              vals: List[np.ndarray],
                              signs: Optional[np.ndarray]):
        """Device-routed exchange: pack RAW rows src-major (a positional
        [S, C] chop — no argsort, no combiner, no per-owner layout) and
        let the fused route+scatter+reduce program derive owners, build
        the all_to_all cells and reduce duplicates on device. Host work
        per flush: one pad + one bincount (cell-rung sizing)."""
        n = len(slots)
        S = self.n_shards
        cap = self.capacity
        with timeline.phase("agg.pack", n=n):
            C = self._rung_chunk.fit(-(-n // S))  # rows per source shard
            N = C * S
            # padding rows: owner spread evenly, local = scratch, valid 0
            enc = np.empty(N, dtype=np.int64)
            enc[:n] = slots
            pad_pos = np.arange(n, N, dtype=np.int64)
            enc[n:] = (pad_pos % S) * STRIDE + (cap - 1)
            valid = np.zeros(N, dtype=np.int64)
            valid[:n] = 1 if signs is None else signs
            if self.salted:
                # positional round-robin spread: every (src, dst) cell
                # holds exactly ceil(C / S) rows — no skew, no bincount
                R = -(-C // S)
                busiest = -(-n // S)
            else:
                owners = enc // STRIDE
                srcs = np.arange(N, dtype=np.int64) // C
                cells = np.bincount(srcs * S + owners, minlength=S * S)
                R = min(self._rung_cell.fit(int(cells.max())), C)
                # real rows per destination: the cells' less the filler's
                to = np.arange(S)
                filler = (N - 1 - to) // S - (n - 1 - to) // S
                busiest = int((cells.reshape(S, S).sum(axis=0)
                               - filler).max())
            inputs = []
            vi = 0
            for op, dt, src, si in self.phys:
                if src == "one":
                    continue
                v = np.full(
                    N,
                    0 if op == "add" else self._neutral(op, dt),
                    dtype=self._dt(dt),
                )
                v[:n] = vals[vi]
                vi += 1
                inputs.append(self._to_dev(v.reshape(S, C), True))
            enc_d = self._to_dev(enc.reshape(S, C), True)
            valid_d = self._to_dev(valid.reshape(S, C), True)
        MESH_STATS["dispatches"] += 1
        # exchange-layer filler: rung padding (N - n) plus all_to_all
        # cell padding (S*S*R - N); both ride the collective
        shipped = max(S * S * R, N)
        self._note_traffic(n, shipped, "mesh.route", R, busiest)
        with timeline.phase("agg.enqueue"):
            self.state = self._route_step(C, R)(
                self.state, enc_d, valid_d, *inputs,
                rung=R, rows=n, padded=shipped,
            )

    def _note_traffic(self, sent: int, shipped: int, program: str,
                      rung: int, busiest: int):
        """One exchange step's counts: `sent` real rows in buffers of
        `shipped`, `busiest` of them owned by the destination shard that
        took the most."""
        self.rows_sent += sent
        self.rows_padded += shipped - sent
        MESH_STATS["rows_sent"] += sent
        MESH_STATS["rows_padded"] += shipped - sent
        MESH_STATS["rows_busiest"] += busiest
        timeline.note("mesh.ship", 0.0, n=sent, padded=shipped)
        timeline.note("mesh.ship.hot", 0.0, n=busiest, padded=sent)
        # per-(program, rung) waste gauge: which packing rungs the
        # exchange actually hits and how much filler each ships
        obs_device.note_padding(program, rung, sent, shipped)

    def _dispatch(self, step, shape, rows, flat, locals_, vals, signs):
        """Pack (slots, valid, per-source values) buffers of `shape` and
        run one jitted step. Buffers enter the device sharded on dim 0
        (the destination-shard dimension). `vals` holds
        one value array per non-count physical accumulator, pre-extracted
        at update() time so buffered flushes just concatenate."""
        MESH_STATS["dispatches"] += 1
        total = int(np.prod(shape))
        with timeline.phase("agg.pack", n=len(rows)):
            slots_l = np.full(total, self.capacity - 1, dtype=np.int64)
            slots_l[flat] = locals_[rows]
            valid = np.zeros(total, dtype=np.int64)
            valid[flat] = 1 if signs is None else signs[rows]
            inputs = []
            vi = 0
            for op, dt, src, si in self.phys:
                if src == "one":
                    continue
                v = np.full(
                    total,
                    0 if op == "add" else self._neutral(op, dt),
                    dtype=self._dt(dt),
                )
                # sign application happens in-kernel: add-sources
                # multiply by valid (0 padding / ±1 append-retract)
                v[flat] = vals[vi][rows]
                vi += 1
                inputs.append(self._to_dev(v.reshape(shape), True))
            slots_d = self._to_dev(slots_l.reshape(shape), True)
            valid_d = self._to_dev(valid.reshape(shape), True)
        with timeline.phase("agg.enqueue"):
            self.state = step(
                self.state, slots_d, valid_d, *inputs,
                rung=shape[-1], rows=len(rows), padded=total,
            )

    def _direct_step(self):
        return self._program("step_direct", self._make_direct_step)

    def _route_step(self, C: int, R: int):
        return self._program("route", lambda: self._make_route_step(C, R),
                             C, R)

    def _make_direct_step(self):
        """Step for host-fed dst-major [S, R] batches: rows were routed to
        their owner shard at packing time, so each shard scatters its own
        block — no collective in the program at all."""
        jax = get_jax()

        from .mesh import _get_jnp

        jnp = _get_jnp()
        phys = list(self.phys)
        axis = self.axis
        scatter = _scatter_body(phys, jnp, self._neutral)

        def local_update(state_shards, slots, valid, *vals):
            # local views: state [1, cap]; slots/valid/vals [1, R] — this
            # shard's rows, already in place after the sharded transfer
            return scatter(
                state_shards, slots[0], valid[0], [v[0] for v in vals]
            )

        n_state = len(self.phys)

        @partial(jax.jit, donate_argnums=(0,), static_argnums=())
        def mesh_step_direct(state, slots, valid, *vals):
            from jax.sharding import PartitionSpec as P

            f = jax.shard_map(
                local_update,
                mesh=self.mesh,
                in_specs=(
                    tuple(P(axis, None) for _ in range(n_state)),
                    P(axis),
                    P(axis),
                )
                + tuple(P(axis) for _ in vals),
                out_specs=tuple(P(axis, None) for _ in range(n_state)),
            )
            return list(f(tuple(state), slots, valid, *vals))

        return obs_device.InstrumentedJit(
            "mesh.step_direct", mesh_step_direct, exchange=True)

    def _make_route_step(self, C: int, R: int):
        """The fused route+scatter+reduce program of the device-resident
        keyed exchange. Input rows arrive RAW and src-major ([S, C]: a
        positional chop of the flush, NamedSharding over the key mesh);
        per shard the program

          1. routes: derives each row's owner from its global slot
             (shard = slot // STRIDE — the splitmix64 hash assigned at
             directory time; device_owners_for is the equivalent for
             raw key words) — salted layouts spread positionally,
          2. positions: ranks rows within their (src, owner) cell via a
             one-hot running count and scatters them into the [S, R]
             send cells (padding cells carry scratch-slot/neutral rows),
          3. exchanges: `jax.lax.all_to_all` over the mesh axis — the
             collective XLA compiles into the step, riding ICI on real
             chip meshes,
          4. scatter-reduces the received rows into the local state
             shard; duplicate slots reduce IN the scatter (.add/.min/
             .max), which is what replaces the host combiner.

        Signs apply in-kernel (add-sources multiply by the valid word;
        min/max sources replace invalid rows with the op's neutral), so
        raw retraction rows need no host preprocessing either."""
        jax = get_jax()

        from .mesh import _get_jnp

        jnp = _get_jnp()
        phys = list(self.phys)
        axis = self.axis
        S = self.n_shards
        cap = self.capacity
        salted = self.salted
        neutral, dtype = self._neutral, self._dt

        def local_route(state_shards, enc, valid, *vals):
            enc, valid = enc[0], valid[0]
            vals = [v[0] for v in vals]
            if salted:
                pos = jnp.arange(C, dtype=jnp.int64)
                owner = (pos % S).astype(jnp.int64)
                rank = pos // S
            else:
                owner = enc // STRIDE
                # rank within (this src chunk, owner): one-hot running
                # count — dense [C, S] work that vectorizes, where a
                # per-row scatter-count would serialize
                oh = owner[:, None] == jnp.arange(S, dtype=enc.dtype)[None, :]
                rank = jnp.take_along_axis(
                    jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1,
                    owner[:, None].astype(jnp.int32), axis=1,
                )[:, 0].astype(jnp.int64)
            loc = enc % STRIDE
            sidx = (owner * R + rank).astype(jnp.int32)

            def exchange(send):
                return jax.lax.all_to_all(
                    send.reshape(S, R), axis, 0, 0, tiled=True
                ).reshape(-1)

            # send cells: padding rows target the scratch slot with
            # valid 0 / neutral values, so they reduce to no-ops
            recv_loc = exchange(
                jnp.full(S * R, cap - 1, dtype=enc.dtype).at[sidx].set(loc)
            )
            recv_valid = exchange(
                jnp.zeros(S * R, dtype=valid.dtype).at[sidx].set(valid)
            )
            recv_vals = []
            vi = 0
            for op, dt, src, si in phys:
                if src == "one":
                    continue
                fill = 0 if op == "add" else neutral(op, dt)
                recv_vals.append(exchange(
                    jnp.full(S * R, fill, dtype=dtype(dt)).at[sidx].set(
                        vals[vi]
                    )
                ))
                vi += 1
            # scatter-reduce; duplicate slots fold here (the device-side
            # combiner): .add sums sign-weighted rows, .min/.max take
            # extremes over neutral-masked rows
            out = []
            vi = 0
            for (op, dt, src, si), s in zip(phys, state_shards):
                row = s[0]
                if src == "one":
                    v = recv_valid.astype(row.dtype)
                else:
                    v = recv_vals[vi]
                    vi += 1
                    if op == "add":
                        v = (v * recv_valid.astype(v.dtype)).astype(
                            row.dtype
                        )
                    else:
                        v = jnp.where(
                            recv_valid != 0, v, neutral(op, dt)
                        ).astype(row.dtype)
                if op == "add":
                    row = row.at[recv_loc].add(v)
                elif op == "min":
                    row = row.at[recv_loc].min(v)
                else:
                    row = row.at[recv_loc].max(v)
                out.append(row[None, :])
            return tuple(out)

        n_state = len(self.phys)

        @partial(jax.jit, donate_argnums=(0,), static_argnums=())
        def mesh_route(state, enc, valid, *vals):
            from jax.sharding import PartitionSpec as P

            f = jax.shard_map(
                local_route,
                mesh=self.mesh,
                in_specs=(
                    tuple(P(axis, None) for _ in range(n_state)),
                    P(axis, None),
                    P(axis, None),
                )
                + tuple(P(axis, None) for _ in vals),
                out_specs=tuple(P(axis, None) for _ in range(n_state)),
            )
            return list(f(tuple(state), enc, valid, *vals))

        return obs_device.InstrumentedJit("mesh.route", mesh_route,
                                          exchange=True)

    # -- drain --------------------------------------------------------------
    #
    # Emission-side programs (gather / fused gather+reset / reset /
    # restore) are shared process-wide like the steps, their slot
    # buffers are padded on sticky emission rungs, and every read is
    # CHUNKED at tpu.mesh_emission_chunk: a 30k-slot end-of-stream
    # drain re-dispatches the full-chunk program eight times instead of
    # specializing a fresh XLA program for one 32768-wide wave (the
    # round-11 ledger counted 16 gather signatures in a single child,
    # almost all hit exactly once by ramp/drain waves).

    def _emit_rung(self, n: int) -> int:
        # plain arithmetic-ladder bucket (no hysteresis): emission waves
        # are the big stable reads, so quantum rungs keep their padding
        # under ~5% while the signature count stays hard-bounded
        return min(_bucket(n, self._buckets), self._emission_chunk)

    def _chunk_bounds(self, n: int):
        step = self._emission_chunk
        return [(lo, min(lo + step, n)) for lo in range(0, max(n, 1), step)]

    def _pad_slots(self, sh, loc, lo, hi, rung):
        sh_p = np.zeros(rung, dtype=np.int64)
        loc_p = np.full(rung, self.capacity - 1, dtype=np.int64)
        sh_p[: hi - lo] = sh[lo:hi]
        loc_p[: hi - lo] = loc[lo:hi]
        return sh_p, loc_p

    # -- owner-sliced emission ------------------------------------------------
    #
    # The replicated-index emission programs (plain jit, state sharded,
    # indices replicated) make EVERY shard scan EVERY index — the SPMD
    # partitioner's scatter/gather strategy — so a 16k-slot wave costs
    # S x 16k serial index ops on a virtual mesh (measured: 7ms per
    # gather_free dispatch, the single largest mesh cost at 1M events).
    # The owner-sliced path sorts the wave's slots by owner shard ON THE
    # HOST (one argsort per wave — the routing information is free in
    # the slot encoding) and hands each shard ONLY its own [1, L] slice
    # through shard_map, cutting device work back to ~n + padding. Host
    # reorders the gathered block back to union order with one fancy
    # index. Salted accumulators keep the replicated programs (the
    # cross-shard fold genuinely needs every shard per slot), as do
    # multi-process meshes (outputs must land replicated on every host).

    def _sliced_ok(self) -> bool:
        return not self.salted and not self._multiproc

    def _slice_rung(self, n_max: int) -> int:
        return self._rung_slice.fit(max(n_max, 1))

    def _slice_pack(self, slots: np.ndarray, extras=(), fills=()):
        """Sort the wave by owner shard and pack per-shard [S, L] index
        buffers (padding rows target the scratch slot). `extras` are
        row-aligned companion arrays (masks, restore values) packed the
        same way with their `fills`. Returns (loc_sl, extra_sls,
        flat_pos, L) where flat_pos[i] is row i's position in the
        flattened [S*L] device output."""
        S = self.n_shards
        slots = np.asarray(slots)
        sh, loc = self._decompose(slots)
        order = np.argsort(sh, kind="stable")
        sh_s = sh[order]
        counts = np.bincount(sh_s, minlength=S)
        L = self._slice_rung(int(counts.max()))
        starts = np.zeros(S, dtype=np.int64)
        np.cumsum(counts[:-1], out=starts[1:])
        rank = np.arange(len(slots), dtype=np.int64) - starts[sh_s]
        flat_sorted = sh_s * L + rank
        loc_sl = np.full(S * L, self.capacity - 1, dtype=np.int64)
        loc_sl[flat_sorted] = loc[order]
        extra_sls = []
        for arr, fill in zip(extras, fills):
            arr = np.asarray(arr)
            e = np.full(S * L, fill, dtype=arr.dtype)
            e[flat_sorted] = arr[order]
            extra_sls.append(e.reshape(S, L))
        flat_pos = np.empty(len(slots), dtype=np.int64)
        flat_pos[order] = flat_sorted
        return loc_sl.reshape(S, L), extra_sls, flat_pos, L

    def _sliced_gather_program(self):
        def build():
            jax = get_jax()

            axis = self.axis
            n_state = len(self.phys)

            def local(state_shards, loc):
                return tuple(s[0][loc[0]][None, :] for s in state_shards)

            @jax.jit
            def mesh_sgather(state, loc):
                from jax.sharding import PartitionSpec as P

                f = jax.shard_map(
                    local,
                    mesh=self.mesh,
                    in_specs=(
                        tuple(P(axis, None) for _ in range(n_state)),
                        P(axis),
                    ),
                    out_specs=tuple(P(axis) for _ in range(n_state)),
                )
                return list(f(tuple(state), loc))

            return obs_device.InstrumentedJit("mesh.sgather", mesh_sgather)

        return self._program("sgather", build)

    def _sliced_take_program(self):
        """Fused sliced gather + masked reset: serves gather_and_reset
        (mask all-ones) and the sliding drain's gather+free (mask =
        freed-bin rows) with ONE program per slice rung."""
        def build():
            jax = get_jax()

            axis = self.axis
            phys = list(self.phys)
            neutral = self._neutral
            cap = self.capacity
            n_state = len(self.phys)

            def local(state_shards, loc, free):
                outs, new = [], []
                loc_r = None
                for (op, dt, _, _), s in zip(phys, state_shards):
                    row = s[0]
                    outs.append(row[loc[0]][None, :])
                    if loc_r is None:
                        from .mesh import _get_jnp

                        jnp = _get_jnp()
                        loc_r = jnp.where(free[0] != 0, loc[0], cap - 1)
                    new.append(row.at[loc_r].set(neutral(op, dt))[None, :])
                return tuple(outs), tuple(new)

            @partial(jax.jit, donate_argnums=(0,))
            def mesh_stake(state, loc, free):
                from jax.sharding import PartitionSpec as P

                f = jax.shard_map(
                    local,
                    mesh=self.mesh,
                    in_specs=(
                        tuple(P(axis, None) for _ in range(n_state)),
                        P(axis),
                        P(axis),
                    ),
                    out_specs=(
                        tuple(P(axis) for _ in range(n_state)),
                        tuple(P(axis, None) for _ in range(n_state)),
                    ),
                )
                outs, new = f(tuple(state), loc, free)
                return list(outs), list(new)

            return obs_device.InstrumentedJit("mesh.stake", mesh_stake)

        return self._program("stake", build)

    def _sliced_reset_program(self):
        def build():
            jax = get_jax()

            axis = self.axis
            phys = list(self.phys)
            neutral = self._neutral
            n_state = len(self.phys)

            def local(state_shards, loc):
                return tuple(
                    s[0].at[loc[0]].set(neutral(op, dt))[None, :]
                    for (op, dt, _, _), s in zip(phys, state_shards)
                )

            @partial(jax.jit, donate_argnums=(0,))
            def mesh_sreset(state, loc):
                from jax.sharding import PartitionSpec as P

                f = jax.shard_map(
                    local,
                    mesh=self.mesh,
                    in_specs=(
                        tuple(P(axis, None) for _ in range(n_state)),
                        P(axis),
                    ),
                    out_specs=tuple(P(axis, None) for _ in range(n_state)),
                )
                return list(f(tuple(state), loc))

            return obs_device.InstrumentedJit("mesh.sreset", mesh_sreset)

        return self._program("sreset", build)

    def _sliced_restore_program(self):
        def build():
            jax = get_jax()

            axis = self.axis
            n_state = len(self.phys)

            def local(state_shards, loc, *vals):
                return tuple(
                    s[0].at[loc[0]].set(v[0])[None, :]
                    for s, v in zip(state_shards, vals)
                )

            @partial(jax.jit, donate_argnums=(0,))
            def mesh_srestore(state, loc, *vals):
                from jax.sharding import PartitionSpec as P

                f = jax.shard_map(
                    local,
                    mesh=self.mesh,
                    in_specs=(
                        tuple(P(axis, None) for _ in range(n_state)),
                        P(axis),
                    )
                    + tuple(P(axis) for _ in vals),
                    out_specs=tuple(P(axis, None) for _ in range(n_state)),
                )
                return list(f(tuple(state), loc, *vals))

            return obs_device.InstrumentedJit("mesh.srestore", mesh_srestore)

        return self._program("srestore", build)

    def _sliced_read(self, slots: np.ndarray,
                     free: Optional[np.ndarray]) -> List[np.ndarray]:
        """Owner-sliced gather (free=None) or fused gather+masked-reset,
        returning host arrays in the wave's original order."""
        n = len(slots)
        # sub-steps of the caller's close.combine, in the ledger only, as
        # in ops/aggregates.py: the pack and the call, then the read
        with timeline.phase("agg.gather", annotate=False):
            if free is None:
                loc_sl, _, flat_pos, L = self._slice_pack(slots)
                obs_device.note_padding("mesh.sgather", L, n,
                                        self.n_shards * L)
                outs = self._sliced_gather_program()(
                    self.state, self._to_dev(loc_sl, True),
                    rung=L, rows=n, padded=self.n_shards * L,
                )
            else:
                loc_sl, (free_sl,), flat_pos, L = self._slice_pack(
                    slots, (np.asarray(free, dtype=np.int64),), (0,)
                )
                obs_device.note_padding("mesh.stake", L, n,
                                        self.n_shards * L)
                outs, self.state = self._sliced_take_program()(
                    self.state, self._to_dev(loc_sl, True),
                    self._to_dev(free_sl, True),
                    rung=L, rows=n, padded=self.n_shards * L,
                )
        # the device-to-host read: waits for every program queued before
        with timeline.phase("agg.read", n=n, annotate=False):
            return [np.asarray(o).reshape(-1)[flat_pos] for o in outs]

    def _gather_program(self):
        def build():
            jax = get_jax()

            phys = list(self.phys)

            if self.salted:

                def mesh_gather(state, sh, loc):
                    # fold across the shard axis; padding rows point at
                    # the scratch slot, neutral on every shard
                    out = []
                    for (op, dt, _, _), s in zip(phys, state):
                        cols = s[:, loc]
                        if op == "add":
                            out.append(cols.sum(axis=0))
                        elif op == "min":
                            out.append(cols.min(axis=0))
                        else:
                            out.append(cols.max(axis=0))
                    return out
            else:

                def mesh_gather(state, sh, loc):
                    return [s[sh, loc] for s in state]

            if self._multiproc:
                # emission values must be readable on EVERY process:
                # pin the outputs replicated so each host reads its
                # local copy (multihost.to_host)
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                mesh_gather = jax.jit(
                    mesh_gather,
                    out_shardings=NamedSharding(self.mesh, P()),
                )
            else:
                mesh_gather = jax.jit(mesh_gather)
            return obs_device.InstrumentedJit("mesh.gather", mesh_gather)

        return self._program("gather", build)

    def _take_program(self):
        def build():
            jax = get_jax()

            phys = list(self.phys)
            salted = self.salted
            neutral = self._neutral

            def mesh_take(state, sh, loc):
                outs, new = [], []
                for (op, dt, _, _), s in zip(phys, state):
                    if salted:
                        cols = s[:, loc]
                        if op == "add":
                            outs.append(cols.sum(axis=0))
                        elif op == "min":
                            outs.append(cols.min(axis=0))
                        else:
                            outs.append(cols.max(axis=0))
                        # a salted slot's state lives on EVERY shard
                        new.append(s.at[:, loc].set(neutral(op, dt)))
                    else:
                        outs.append(s[sh, loc])
                        new.append(s.at[sh, loc].set(neutral(op, dt)))
                return outs, new

            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            return obs_device.InstrumentedJit(
                "mesh.take",
                jax.jit(
                    mesh_take,
                    donate_argnums=(0,),
                    # outs replicated (each process reads its local
                    # copy), state stays row-sharded
                    out_shardings=(
                        [NamedSharding(self.mesh, P())] * len(self.phys),
                        [self._sharding] * len(self.phys),
                    ),
                ),
            )

        return self._program("take", build)

    def _reset_program(self):
        def build():
            jax = get_jax()

            phys = list(self.phys)
            salted = self.salted
            neutral = self._neutral

            @partial(jax.jit, donate_argnums=(0,),
                     out_shardings=self._sharding)
            def mesh_reset(state, sh, loc):
                if salted:
                    # a salted slot's state lives on EVERY shard
                    return [
                        s.at[:, loc].set(neutral(op, dt))
                        for s, (op, dt, _, _) in zip(state, phys)
                    ]
                return [
                    s.at[sh, loc].set(neutral(op, dt))
                    for s, (op, dt, _, _) in zip(state, phys)
                ]

            return obs_device.InstrumentedJit("mesh.reset", mesh_reset)

        return self._program("reset", build)

    def _restore_program(self):
        def build():
            jax = get_jax()

            phys = list(self.phys)
            salted = self.salted
            neutral = self._neutral

            @partial(jax.jit, donate_argnums=(0,),
                     out_shardings=self._sharding)
            def mesh_restore(state, sh, loc, *vals):
                if salted:
                    # restored value lands whole on the nominal shard;
                    # the other shards go neutral so the cross-shard
                    # fold reproduces it
                    return [
                        s.at[:, loc].set(neutral(op, dt))
                        .at[sh, loc].set(v)
                        for (op, dt, _, _), s, v in zip(phys, state, vals)
                    ]
                return [
                    s.at[sh, loc].set(v) for s, v in zip(state, vals)
                ]

            return obs_device.InstrumentedJit("mesh.restore", mesh_restore)

        return self._program("restore", build)

    def gather(self, slots: np.ndarray,
               materialize: bool = True) -> List[np.ndarray]:
        self._flush_if_touches(slots)
        self._gather_slots = np.asarray(slots)
        self._segment_udaf = None
        self._segment_multiset = None
        n = len(slots)
        if n == 0:
            return [
                np.empty(0, dtype=self._dt(dt))
                for _, dt, _, _ in self.phys
            ]
        if self._sliced_ok():
            return self._sliced_read(np.asarray(slots), None)
        return self._replicated_read(
            self._gather_program(), np.asarray(slots),
            materialize=materialize,
        )

    def _replicated_read(self, prog, slots: np.ndarray,
                         free: Optional[np.ndarray] = None,
                         materialize: bool = True) -> List[np.ndarray]:
        """Chunked read through a replicated-index program (salted and
        multi-process meshes): `mesh.gather` reads, the others also write
        the state they hand back; `free` is `mesh.gather_free`'s mask."""
        from .multihost import to_host

        n = len(slots)
        sh, loc = self._decompose(slots)
        chunks = self._chunk_bounds(n)
        pieces = []
        for lo, hi in chunks:
            # ledger-only sub-steps of the close, as in _sliced_read
            with timeline.phase("agg.gather", annotate=False):
                rung = self._emit_rung(hi - lo)
                sh_p, loc_p = self._pad_slots(sh, loc, lo, hi, rung)
                args = [self._to_dev(sh_p, False),
                        self._to_dev(loc_p, False)]
                if free is not None:
                    free_p = np.zeros(rung, dtype=np.int64)
                    free_p[: hi - lo] = free[lo:hi]
                    args.append(self._to_dev(free_p, False))
                obs_device.note_padding(prog.program, rung, hi - lo, rung)
                outs = prog(self.state, *args, rung=rung, rows=hi - lo)
                if prog.program != "mesh.gather":
                    outs, self.state = outs
            if len(chunks) == 1 and not materialize:
                if self._multiproc:
                    # replicated outputs span remote devices; hand back
                    # this process's local copy so later slicing /
                    # np.asarray work
                    outs = [o.addressable_data(0) for o in outs]
                return [o[:n] for o in outs]
            with timeline.phase("agg.read", n=hi - lo, annotate=False):
                pieces.append([to_host(o)[: hi - lo] for o in outs])
        if len(pieces) == 1:
            return pieces[0]
        return [
            np.concatenate([p[i] for p in pieces])
            for i in range(len(self.phys))
        ]

    def gather_and_reset(self, slots: np.ndarray,
                         materialize: bool = True) -> List[np.ndarray]:
        """Fused drain: ONE jitted program gathers the slots' values and
        writes them back to neutral — the tumbling/session emission path
        otherwise pays two device dispatches per watermark wave, and on
        the CPU mesh every dispatch costs milliseconds of XLA launch.
        Host-side per-slot state is NOT dropped here: the caller
        finalizes first (finalize reads the stores), then calls
        drop_host_state."""
        self._flush_if_touches(slots)
        self._gather_slots = np.asarray(slots)
        self._segment_udaf = None
        self._segment_multiset = None
        n = len(slots)
        if n == 0 or not self.phys:
            return [
                np.empty(0, dtype=self._dt(dt))
                for _, dt, _, _ in self.phys
            ]
        if self._sliced_ok():
            return self._sliced_read(
                np.asarray(slots), np.ones(n, dtype=np.int64)
            )
        return self._replicated_read(
            self._take_program(), np.asarray(slots),
            materialize=materialize,
        )

    def _gather_free_program(self):
        """Fused sliding drain: gather the window union AND reset the
        freed-bin subset (a 0/1 mask over the same padded slot buffer)
        in ONE jitted dispatch — the per-wave gather + reset pair
        otherwise costs two sharded-program launches, and the mask rides
        the gather's rung so the fusion adds NO shape signatures."""
        def build():
            jax = get_jax()

            phys = list(self.phys)
            salted = self.salted
            neutral = self._neutral

            def mesh_gather_free(state, sh, loc, free):
                outs, new = [], []
                # masked-out rows redirect their reset to the scratch
                # slot (already neutral), so one program serves every
                # (gather rung, freed count) combination
                loc_r = jax.numpy.where(free != 0, loc,
                                        state[0].shape[1] - 1)
                sh_r = jax.numpy.where(free != 0, sh, 0)
                for (op, dt, _, _), s in zip(phys, state):
                    if salted:
                        cols = s[:, loc]
                        if op == "add":
                            outs.append(cols.sum(axis=0))
                        elif op == "min":
                            outs.append(cols.min(axis=0))
                        else:
                            outs.append(cols.max(axis=0))
                        # a salted slot's state lives on EVERY shard
                        new.append(s.at[:, loc_r].set(neutral(op, dt)))
                    else:
                        outs.append(s[sh, loc])
                        new.append(s.at[sh_r, loc_r].set(neutral(op, dt)))
                return outs, new

            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            return obs_device.InstrumentedJit(
                "mesh.gather_free",
                jax.jit(
                    mesh_gather_free,
                    donate_argnums=(0,),
                    out_shardings=(
                        [NamedSharding(self.mesh, P())] * len(self.phys),
                        [self._sharding] * len(self.phys),
                    ),
                ),
            )

        return self._program("gather_free", build)

    def combine_for_segments_and_free(
        self, slots: np.ndarray, seg_ids: np.ndarray, n_segments: int,
        free_n: int = 0,
    ) -> List[np.ndarray]:
        if free_n == 0 or not self.phys:
            return super().combine_for_segments_and_free(
                slots, seg_ids, n_segments, free_n
            )
        slots = np.asarray(slots)
        n = len(slots)
        self._flush_if_touches(slots)
        self._gather_slots = slots
        self._segment_udaf = None
        self._segment_multiset = None
        free = np.zeros(n, dtype=np.int64)
        free[:free_n] = 1
        if self._sliced_ok():
            gathered = self._sliced_read(slots, free)
        else:
            gathered = self._replicated_read(
                self._gather_free_program(), slots, free
            )
        combined = self._combine_gathered(gathered, slots, seg_ids,
                                          n_segments)
        # host-side per-slot state of the freed bin drops AFTER the
        # segment maps above captured it (reset_slots would do the same)
        self._drop_udaf_slots(slots[:free_n])
        return combined

    def reset_slots(self, slots: np.ndarray):
        self._flush_if_touches(slots)
        self._drop_udaf_slots(slots)
        n = len(slots)
        if n == 0 or not self.phys:
            return
        with timeline.phase("agg.reset", annotate=False):
            self._reset_device(np.asarray(slots))

    def _reset_device(self, slots: np.ndarray):
        if self._sliced_ok():
            loc_sl, _, _, L = self._slice_pack(slots)
            self.state = self._sliced_reset_program()(
                self.state, self._to_dev(loc_sl, True), rung=L,
            )
            return
        prog = self._reset_program()
        sh, loc = self._decompose(slots)
        for lo, hi in self._chunk_bounds(len(slots)):
            rung = self._emit_rung(hi - lo)
            sh_p, loc_p = self._pad_slots(sh, loc, lo, hi, rung)
            self.state = prog(
                self.state, self._to_dev(sh_p, False),
                self._to_dev(loc_p, False), rung=rung,
            )

    def restore(self, slots: np.ndarray, values: List[np.ndarray]):
        self._flush_if_touches(slots)
        values = self._restore_udaf_cols(slots, values)
        n = len(slots)
        if n == 0 or not self.phys:
            return
        if self._sliced_ok():
            vals = [
                np.asarray(v).astype(self._dt(dt), copy=False)
                for (op, dt, _, _), v in zip(self.phys, values)
            ]
            loc_sl, val_sls, _, L = self._slice_pack(
                np.asarray(slots), tuple(vals),
                tuple(self._neutral(op, dt)
                      for op, dt, _, _ in self.phys),
            )
            self.state = self._sliced_restore_program()(
                self.state, self._to_dev(loc_sl, True),
                *[self._to_dev(v, True) for v in val_sls], rung=L,
            )
            return
        prog = self._restore_program()
        sh, loc = self._decompose(np.asarray(slots))
        # pad on the emission rungs like gather/reset so restore chunk
        # sizes don't each specialize the jitted scatter; padding rows
        # write the neutral value into the scratch slot
        for lo, hi in self._chunk_bounds(n):
            rung = self._emit_rung(hi - lo)
            sh_p, loc_p = self._pad_slots(sh, loc, lo, hi, rung)
            vals_p = []
            for (op, dt, _, _), v in zip(self.phys, values):
                vp = np.full(rung, self._neutral(op, dt),
                             dtype=self._dt(dt))
                vp[: hi - lo] = np.asarray(v)[lo:hi]
                vals_p.append(vp)
            self.state = prog(
                self.state,
                self._to_dev(sh_p, False),
                self._to_dev(loc_p, False),
                *[self._to_dev(v, False) for v in vals_p],
                rung=rung,
            )
