"""Device milliseconds per training step in the program's ``decoder``
scope outside its ``attention`` and ``mlp`` scopes: the layer scan's own
norms, residual adds, per-layer slicing and stacking, all passes, over
the traced window (``scopes.py``)."""

from benchmarks.chip import scopes


def read(rec):
    return scopes.layer_ms(rec, ("decoder",))
