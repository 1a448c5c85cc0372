"""Device milliseconds per training step that full rematerialization
spends recomputing the forward inside the backward (the recompute pass,
in any scope or none), over the traced window (``scopes.py``)."""

from benchmarks.chip import scopes


def read(rec):
    return scopes.layer_ms(rec, passes=("recompute",))
