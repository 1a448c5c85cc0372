"""Run one benchmark cell on the chip and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window is traced and they are its per-layer
metrics.  The last line of standard output is one JSON object (correct,
attempted, failed, metrics, device, breakdown, checks); each compared
number is also printed beside its limit as the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits nonzero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
# the script's own directory would shadow the standard library's ``trace``
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]

from benchmarks.chip import bench  # noqa: E402
from benchmarks.chip import trace as trace_lib  # noqa: E402


class Window:
    """The measured window: host clock, and the profiler when tracing."""

    def __init__(self, run):
        self.run = run
        self.t0 = self.t1 = 0.0
        self._spans = None

    def span(self, name: str):
        if self._spans is None:
            return contextlib.nullcontext()
        return self._spans(name)

    def __enter__(self):
        jax = self.run.jax
        self.compiles_before = self.run.compiles
        if self.run.trace:
            shutil.rmtree(self.run.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.run.trace_dir),
                                     profiler_options=opts)
            self._spans = jax.profiler.TraceAnnotation
            self._win = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
            self._win.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.run.trace:
            self._win.__exit__(*exc)
            self.run.jax.profiler.stop_trace()
            if exc[0] is None:
                self.run.trace_data = trace_lib.load(
                    trace_lib.find_xplane(str(self.run.trace_dir)))
            shutil.rmtree(self.run.trace_dir, ignore_errors=True)
        n = self.run.compiles - self.compiles_before
        if exc[0] is None and n:
            raise bench.BenchError(f"{n} programs compiled inside the "
                                   "measured window")
        return False


class Run:
    """What a kind's timed loop reads and fills in."""

    def __init__(self, cell, args, jax, devices, peaks):
        self.cell, self.jax, self.devices, self.peaks = cell, jax, devices, \
            peaks
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.trace_dir = cell.root / ".bench_out" / "trace"
        self.trace_data = None
        self.metrics: dict = {}
        self.checks: list[tuple[str, float, float]] = []
        self.attempted = self.failed = 0
        self.host: dict = {}
        self.device = bench.device_record(devices)
        self.compiles = 0
        self.memory_samples: dict[str, int] = {}

        def on_compile(event, duration, **_):
            if event in ("/jax/core/compile/backend_compile_duration",
                         "/jax/compilation_cache/cache_retrieval_time_sec"):
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_compile)

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", flush=True)

    def setup_done(self) -> None:
        self.metric("setup_s", time.perf_counter() - T_START, "s")
        # what set-up left on the heap (traced programs, caches) stays out
        # of the collector's full passes, which would otherwise pause the
        # host inside the window
        gc.collect()
        gc.freeze()
        self.log(f"set-up {self.metrics['setup_s']['value']:.3f}s, "
                 f"{self.compiles} programs compiled or loaded")

    def window(self) -> Window:
        return Window(self)

    def metric(self, name: str, value: float, unit: str) -> None:
        self.metrics[name] = {"value": float(value), "unit": unit}

    def read_memory(self) -> None:
        self.device = bench.device_record(self.devices)

    def sample_memory(self, what: str) -> None:
        """Read the device allocator once per chip: the buffers in use and
        the memory it holds reserved for the loaded programs' temporaries
        (which buffers in use leave out), at the same instant.  The fullest
        chip's sum is one footprint; ``peak_hbm_gib`` is the largest of the
        samples taken after set-up and after the window."""
        worst = (0, 0, 0)
        for d in self.devices:
            s = d.memory_stats()
            if s is None:            # a CPU device in the harness's tests
                continue
            if "bytes_reserved" not in s:
                raise bench.BenchError(f"the device reports no reserved "
                                       f"bytes: {sorted(s)}")
            used, held = int(s["bytes_in_use"]), int(s["bytes_reserved"])
            worst = max(worst, (used + held, used, held))
        self.memory_samples[what] = worst[0]
        self.log(f"memory {what}: {worst[1]} bytes in use + {worst[2]} "
                 f"reserved = {worst[0] / 2**30:.4f} GiB")

    def hbm_footprint_bytes(self) -> int:
        return max(self.memory_samples[w]
                   for w in ("after set-up", "after the window"))

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.checks)


def per_layer(run) -> dict:
    """Read each per-layer metric of the cell from the trace and the
    harness's host records; a reader that finds nothing is left out."""
    rec = {"trace": run.trace_data, "host": run.host, "peaks": run.peaks,
           "config": run.cell.config, "workload": run.cell.workload,
           "bench_dir": run.cell.bench_dir}
    out = {}
    for m in run.cell.per_layer:
        reader = bench.load_module(run.cell.bench_dir / "metrics" /
                                   f"{m['name']}.py")
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, *, root: Path = ROOT, bench_dir: Path = bench.BENCH_DIR,
         chip_check=bench.require_chips) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = bench.find_cell(root, args.workload, bench_dir)
        import jax

        devices = chip_check(jax, cell.chips)
        peaks = bench.peaks_for(devices[0].device_kind, bench_dir)
        sys.path.insert(0, str(root / "src"))
        try:
            from repro.launch.compile_cache import enable_compile_cache
        except ImportError as e:
            raise bench.BenchError(f"the system under test is not in this "
                                   f"checkout: {e}") from None
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cache = enable_compile_cache()
        run = Run(cell, args, jax, devices, peaks)
        run.log(f"{cell.name} seed {args.seed} on {len(devices)} x "
                f"{devices[0].device_kind}; compile cache {cache}")
        kind = bench.load_module(bench_dir / "kinds" /
                                 f"{cell.workload['kind']}.py")
        kind.run(run)
    except bench.BenchError as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr, flush=True)
        return 2
    gc.collect()
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed}
    if run.trace:
        result["metrics"] = per_layer(run)
        run.device["busy_s"] = trace_lib.busy_s(run.trace_data)
        run.device["window_s"] = run.trace_data.window_s
        result["device"] = run.device
        result["breakdown"] = {
            "device_ops": trace_lib.top_ops(run.trace_data),
            "idle_gaps": trace_lib.idle_gaps(run.trace_data)}
    else:
        names = [m["name"] for m in cell.end_to_end]
        result["metrics"] = {k: v for k, v in run.metrics.items()
                             if k in names}
        result["device"] = run.device
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in run.checks}
    for n, v, lim in run.checks:
        print(f"[check] {n} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'OVER'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
