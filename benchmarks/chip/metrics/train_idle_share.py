"""Share of the traced training window in which no operation ran on the
device: 1 - (union of device op intervals) / window, in percent."""

from benchmarks.chip import trace as trace_lib


def read(rec):
    tr = rec["trace"]
    if tr is None or rec["host"].get("kind") != "train" or not tr.ops:
        return None
    return 100.0 * (1.0 - trace_lib.busy_s(tr) / tr.window_s)
