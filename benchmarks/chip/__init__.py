"""On-chip benchmark of SubTrack++ training.

Run one cell from the root of a checkout:

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it: ``configs/<config>.json``,
``workloads/<cell>.json``, ``kinds/<kind>.py`` and ``metrics/<metric>.py``;
the model family that a configuration's ``reference`` names is
``refs/<reference>.py``.
"""
