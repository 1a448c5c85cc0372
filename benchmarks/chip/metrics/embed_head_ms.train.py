"""Device milliseconds per training step in the program's ``embed`` and
``head_loss`` scopes (token lookup and its gradient's scatter-add; final
norm, the vocabulary head and the loss), all passes, over the traced
window (``scopes.py``)."""

from benchmarks.chip import scopes


def read(rec):
    return scopes.layer_ms(rec, ("embed", "head_loss"))
