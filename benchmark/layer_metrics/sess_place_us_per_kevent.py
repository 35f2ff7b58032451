"""Host time of the session operator's bookkeeping per thousand events
accepted in the window: the ledger's `sess.segment` (a batch's live rows
sorted by key and time and cut where the key changes or two rows lie the
gap or more apart) and `sess.place` (the segments' keys looked up together,
hits extended, misses opened from one slot allocation; only a key with
several open sessions goes one segment at a time), by their total seconds
(`operators/windows.py` `SessionWindowOperator.process_batch`). Read per
task, in the operator that placed the most segments, as `dir_new_slot_pct`
is. A program that books neither gives None."""

import ledger_tasks
import ledger_window


def read(run):
    t = ledger_tasks.of_largest(run, "sess.place")
    if not t:
        return None
    secs = sum(t[p]["total_s"] for p in ("sess.segment", "sess.place")
               if p in t)
    return ledger_window.us_per_kevent(run, secs)
