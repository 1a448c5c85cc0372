"""Device milliseconds per training step in the program's ``mlp`` scope
(gate, up and down matmuls and the activation), all passes, over the
traced window (``scopes.py``)."""

from benchmarks.chip import scopes


def read(rec):
    return scopes.layer_ms(rec, ("mlp",))
