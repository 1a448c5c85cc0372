"""Device milliseconds per training step in the program's ``clip``,
``optimizer`` and ``apply`` scopes: the global-norm clip, all of the
SubTrack++ update (Pallas kernels and XLA work) and the guarded apply,
read as one layer because the apply's select fuses into the optimizer's
last writes, over the traced window (``scopes.py``)."""

from benchmarks.chip import scopes


def read(rec):
    return scopes.layer_ms(rec, ("clip", "optimizer", "apply"))
