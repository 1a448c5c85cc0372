"""Readings that the limits of ``correct`` are set from, on the chip at the
cell's own size.  Not part of a benchmark run.

    python benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 12 --control-seeds 3

For each seed, the program's set-up and three checked steps against the
float32 reference (the lower readings).  On the first ``--control-seeds``
seeds also: the reference in float8 put in the program's place (the
control); the reference with half of the batch left out (a fault); and the
program with its warm start at the TPU's default matmul precision, as the
program's own path runs it (PERF.md, Open question 1).  Prints one JSON
object per reading and a summary.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import bench, model as model_lib  # noqa: E402


def emit(**kw):
    print(json.dumps(kw), flush=True)


def train(cell, seeds, control_seeds):
    import numpy as np

    from benchmarks.chip.kinds import train as tk

    conf, wl = cell.config, cell.workload
    prog = tk.Program(conf, wl)
    names = tk.NUMBERS
    lows, ups = {n: [] for n in names}, {}
    for k, s in enumerate(seeds):
        seed = model_lib.seed32(s)
        batch = prog.data(seed)
        t = time.perf_counter()
        state, _, rd = prog.start(seed, batch)
        del state
        gc.collect()
        rd["change"] = tk.change_norms(conf, seed, rd.pop("params"),
                                       rd.pop("paths"))
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        refr = tk.reference_readings(conf, wl, seed, batch)
        t_ref = time.perf_counter() - t
        c = tk.compare(rd, refr)
        emit(what="program", seed=s, **{n: c[n] for n in names},
             step_loss_gaps=c["step_loss_gaps"],
             grad_at=c["grad_gap_at"], change_at=c["change_gap_at"],
             losses=rd["losses"], ref_losses=refr["losses"],
             left_out=c["left_out"], program_s=t_prog, reference_s=t_ref)
        for n in names:
            lows[n].append(c[n])
        if k >= control_seeds:
            continue
        S = wl["seq_len"]
        for what, kw in (("control_fp8", {"fmt": "fp8"}),
                         ("half_batch", {"rows": np.arange(S) < S // 2}),
                         ("default_precision", None)):
            if kw is None:
                state, _, low = prog.start(seed, batch, precision="default")
                del state
                gc.collect()
                low["change"] = tk.change_norms(conf, seed, low.pop("params"),
                                                low.pop("paths"))
            else:
                low = tk.reference_readings(conf, wl, seed, batch, **kw)
            c = tk.compare(low, refr)
            emit(what=what, seed=s, **{n: c[n] for n in names},
                 step_loss_gaps=c["step_loss_gaps"],
                 grad_at=c["grad_gap_at"], change_at=c["change_gap_at"])
            for n in names:
                ups.setdefault(what, {}).setdefault(n, []).append(c[n])
    emit(what="summary", lower={n: max(v) for n, v in lows.items()},
         upper={w: {n: min(v) for n, v in d.items()} for w, d in ups.items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    bench.require_chips(jax, 1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    cell = bench.find_cell(ROOT, args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    train(cell, seeds, args.control_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
