"""Device milliseconds per training step in the program's ``attention``
scope (Q/K/V/O projections, RoPE and the blocked attention), forward,
backward and recompute, over the traced window (``scopes.py``)."""

from benchmarks.chip import scopes


def read(rec):
    return scopes.layer_ms(rec, ("attention",))
