#!/usr/bin/env python
"""ASan/UBSan build-and-run for the native slot directory.

SURVEY.md §5.2: the reference relies on Rust's ownership model for
memory safety; our host C++ (native/slotdir.cpp — hand-rolled open
addressing + manual refcounts on the hot path of every window operator)
gets sanitizers instead. This script:

  1. compiles slotdir.cpp with -fsanitize=address,undefined into a
     scratch directory,
  2. runs an exercise workload (random assign/take/get/entries cycles,
     single- and multi-key, growth past the initial capacity, freed-slot
     reuse) in a child python under LD_PRELOAD=libasan, verifying
     results against the pure-python SlotDirectory,
  3. exits nonzero on any sanitizer report or mismatch.

Wired into the suite as tests/test_native_sanitizer.py; run manually:
    python tools/sanitize_native.py
"""

import os
import subprocess
import sys
import sysconfig
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "native", "slotdir.cpp")

EXERCISE = r"""
import numpy as np

import arroyo_native  # the sanitized build (scratch dir is first on path)

from arroyo_tpu.ops.directory import SlotDirectory
from arroyo_tpu.ops.native import NativeSlotDirectory

rng = np.random.default_rng(17)
for n_keys in (1, 3):
    nat = NativeSlotDirectory(arroyo_native, n_keys=n_keys)
    ref = SlotDirectory()
    for step in range(60):
        n = int(rng.integers(1, 700))
        bins = rng.integers(0, 6, n)
        keys = [rng.integers(-5000, 5000, n) for _ in range(n_keys)]
        s_nat = nat.assign(bins, keys)
        s_ref = ref.assign(bins, keys)
        # same grouping structure (slot numbering may differ)
        import numpy as _np
        _, inv_a = _np.unique(s_nat, return_inverse=True)
        _, inv_b = _np.unique(s_ref, return_inverse=True)
        pairs = set(zip(inv_a.tolist(), inv_b.tolist()))
        assert len(pairs) == len({a for a, _ in pairs}) == len(
            {b for _, b in pairs}
        ), f"grouping diverged at step {step}"
        if step % 13 == 7:
            # reverse index resolves to the EXACT (bin, key) of the
            # input rows (a stale slot_owner after entry recycling is
            # the bug class this structure can have)
            kk = nat.keys_for_slots(s_nat[:50])
            for i, entry in enumerate(kk):
                assert entry is not None, f"live slot unresolved at {step}"
                got_bin, got_key = entry
                assert got_bin == int(bins[i]), f"wrong bin at {step}"
                assert got_key == tuple(
                    int(c[i]) for c in keys
                ), f"wrong key at {step}"
            # targeted removal; freed slots must then resolve to None
            b = int(rng.integers(0, 6))
            pk = ref.peek_bin(b) or {}
            victims = list(pk.keys())[:20]
            nat_map = nat.slots_for_keys(b, victims)
            assert set(nat_map) == set(victims), f"lookup at {step}"
            f_nat = nat.remove(b, victims)
            f_ref = ref.remove(b, victims)
            assert len(f_nat) == len(f_ref), f"remove at {step}"
            assert sorted(int(s) for s in f_nat) == sorted(
                nat_map.values()
            ), f"freed slots disagree with lookup at {step}"
            gone = nat.keys_for_slots(np.asarray(f_nat))
            assert all(g is None for g in gone), (
                f"freed slot still resolves at {step}"
            )
        if step % 7 == 3:
            b = int(rng.integers(0, 6))
            ka, sa = nat.take_bin(b)
            kb, sb = ref.take_bin(b)
            assert sorted(ka) == sorted(kb), f"take_bin keys at {step}"
        if step % 11 == 5:
            b = int(rng.integers(0, 6))
            ents = nat.bin_entries(b)
            pk = ref.peek_bin(b) or {}
            assert len(ents[1]) == len(pk), f"bin_entries at {step}"
        assert nat.n_live == ref.n_live, f"n_live at {step}"
    list(nat.items())  # exercise entries() buffers
print("SANITIZED-OK")
"""


def main() -> int:
    include = sysconfig.get_paths()["include"]
    libasan = subprocess.run(
        ["g++", "-print-file-name=libasan.so"], capture_output=True,
        text=True,
    ).stdout.strip()
    if not libasan or not os.path.exists(libasan):
        print("libasan not found; skipping", file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(
            td, f"arroyo_native{sysconfig.get_config_var('EXT_SUFFIX')}"
        )
        cmd = [
            "g++", "-O1", "-g", "-std=c++17", "-shared", "-fPIC",
            "-fsanitize=address,undefined", "-fno-omit-frame-pointer",
            f"-I{include}", SRC, "-o", out,
        ]
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        env = dict(os.environ)
        env["LD_PRELOAD"] = libasan
        # CPython leaks deliberately at exit; halt hard on real errors
        env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
        env["UBSAN_OPTIONS"] = "halt_on_error=1:print_stacktrace=1"
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PYTHONPATH", None)
        # td inserted LAST so the sanitized build shadows any repo-level
        # arroyo_native on the path
        script = (
            f"REPO = {REPO!r}\n"
            f"import sys; sys.path.insert(0, REPO); "
            f"sys.path.insert(0, {td!r})\n" + EXERCISE
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True,
            text=True, timeout=300,
        )
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0 or "SANITIZED-OK" not in proc.stdout:
            print(f"sanitizer run failed rc={proc.returncode}",
                  file=sys.stderr)
            return 1
    print("native sanitizer run clean (ASan+UBSan)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
