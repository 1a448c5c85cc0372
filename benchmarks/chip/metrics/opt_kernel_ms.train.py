"""Device milliseconds per plain training step in the optimizer's Pallas
kernels (``kernels/grassmann.py`` via ``kernels/ops.py``), over the traced
training window."""

import re
import sys

from benchmarks.chip import trace as trace_lib

# How the optimizer's kernels show in a TPU trace, read by hand from traced
# training runs: each Pallas launch is a custom call to "tpu_custom_call"
# whose instruction is named after the kernel's entry in
# ``kernels/grassmann.py`` (``%project_colnorms.1``) or, for the stacked
# buckets of same-shape leaves, ``%vmap__.N``.
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
OPTIMIZER_KERNELS = frozenset({
    "vmap__", "project", "backproject", "tangent", "recovery",
    "project_colnorms", "project_tangent_colnorms", "grad_tap",
    "tangent_gram", "fused_update", "adam_lowrank", "adam_lowrank_norms"})
# Every one of them reads or writes the float32 basis (m, r) or a moment
# (r, n): the rank is one of the last two sizes of a float32 array.
_F32 = re.compile(r"f32\[((?:\d+,)*\d+)\]")


def instruction(name: str) -> str:
    """``%vmap__.11 = ...`` -> ``vmap__``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def is_optimizer_kernel(name: str, rank: int) -> bool:
    if KERNEL_TARGET not in name or instruction(name) not in OPTIMIZER_KERNELS:
        return False
    return any(rank in [int(x) for x in dims.split(",")][-2:]
               for dims in _F32.findall(name))


def kernel_seconds(tr, rank: int) -> float:
    """Device seconds of the optimizer's kernels in the window.  A Pallas
    kernel that is not the optimizer's is left out, and named on standard
    error, so that it never counts as optimizer time."""
    seconds = trace_lib.op_seconds(tr, lambda n: is_optimizer_kernel(n, rank))
    other = {}
    for ops in tr.ops:
        for s, e, n in ops:
            if KERNEL_TARGET in n and not is_optimizer_kernel(n, rank):
                key = trace_lib.short_name(n)
                other[key] = other.get(key, 0.0) + (e - s) / len(tr.ops)
    if other:
        print(f"[opt_kernel_ms.train] Pallas kernels not counted as the "
              f"optimizer's: {sorted(other.items())}", file=sys.stderr)
    return seconds


def read(rec):
    tr, host = rec["trace"], rec["host"]
    if tr is None or host.get("kind") != "train" or not host["steps"]:
        return None
    seconds = kernel_seconds(tr, rec["workload"]["optimizer"]["rank"])
    if not seconds:
        return None
    return 1e3 * seconds / host["steps"]
