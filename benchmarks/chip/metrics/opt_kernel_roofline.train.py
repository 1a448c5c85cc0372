"""The optimizer kernels' share of their roofline over the traced training
window, in percent: the least time of the work any plain low-rank update
must do, over the kernels' device time per step.

Work per low-rank matrix (m x n, rank r; ``kinds/train.lowrank_shapes``):
read and write W in bfloat16 (4mn bytes), read S (4mr), read and write M
and V in float32 (16rn), and the back-projection's 2mnr FLOPs.  The
gradient's read and the projection are left out, because the backward
may absorb them; dense-Adam leaves and XLA fusions are not counted.  The
least time of each matrix is the larger of FLOPs over peak FLOP/s and
bytes over peak bandwidth, summed over the matrices."""

from benchmarks.chip import bench


def read(rec):
    tr, host = rec["trace"], rec["host"]
    if tr is None or host.get("kind") != "train" or not host["steps"]:
        return None
    kernel = bench.load_module(bench.BENCH_DIR / "metrics" /
                               "opt_kernel_ms.train.py")
    rank = rec["workload"]["optimizer"]["rank"]
    seconds = kernel.kernel_seconds(tr, rank) / host["steps"]
    if not seconds:
        return None
    peaks = rec["peaks"]
    least = 0.0
    for m, n, r in host["lowrank"]:
        nbytes = 4 * m * n + 4 * m * r + 16 * r * n
        least += max(2.0 * m * n * r / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
