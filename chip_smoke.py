#!/usr/bin/env python3
"""On-chip smoke check: the trainer and the paged server at full width.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip (the default):

1. Trainer.  ``repro.launch.train.train`` runs llama-1b (24 layers,
   d_model 2048, 32 heads, rank 512) for 6 steps at batch 8 x seq 256
   with a tracking step every 3 steps, three times from the same seed:
   with the fused kernels and the grad-fused backward, with the fused
   kernels alone, and on the pure-jnp reference path.  Every loss must be
   finite, no step may be quarantined, rolled back or skipped, a tracking
   step must run, the kernel runs must dispatch no reference fallback, and
   the kernel runs' loss histories must agree with the reference run's
   within ``LOSS_TOL``.
2. Server.  ``repro.launch.serve.serve`` runs qwen1.5-4b (40 layers,
   d_model 2560, head dim 128) on the paged engine: 8 requests, prompt 64,
   16 new tokens, prefill chunks of 32.  Every request must finish on the
   paged engine, the paged-attention kernel must have been dispatched
   compiled, and the kernel must match ``ref.paged_attention_ref`` at the
   served shapes within ``PAGED_TOL``.

``--four-chips`` runs only the mesh path: qwen1.5-4b training on a (1, 4)
mesh with the mesh-native fused hot path (``--hotpath-layout auto``:
column / row / row-rs StepPrograms per leaf) and, for comparison, with the
hot path off (GSPMD over the production TP rules).  It prints each leaf's
regime and every device's peak memory.

Weights are random, drawn from the run's seed.  Step times printed here
are a smoke reading, not a benchmark.  The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every check passed; without a TPU the script exits nonzero first.  All
phases run in this one process (a second process could not open the
chip).
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Published per-chip peaks, keyed by jax's device_kind.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GiB HBM at
# 819 GB/s).  A device kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
}

# Kernel-vs-reference loss agreement (absolute, per step).  The two paths
# run the same optimizer arithmetic in different summation orders (and the
# reference path's fp32 matmuls run at XLA's default TPU precision), and
# each step's update is rounded into bf16 parameters, whose spacing is
# 2^-8 relative; so the histories drift apart by rounding alone.  0.02 is
# 0.2% of the initial loss ln(32000) = 10.37 and about 20x the drift the
# same comparison shows at CPU smoke size (1.1e-3 over 6 steps); a kernel
# that drops, doubles or misplaces the low-rank update moves the loss by
# far more.
LOSS_TOL = 2e-2

# Paged kernel vs oracle at the served shapes, max |diff| / max |ref|:
# the kernel writes bf16 (spacing 2^-8 relative) and the oracle's fp32
# einsums run at XLA's default TPU precision (one bf16 pass).
PAGED_TOL = 1e-2

TRAIN_ARGS = ["--arch", "llama-1b", "--optimizer", "subtrack",
              "--steps", "6", "--update-interval", "3", "--batch", "8",
              "--seq", "256", "--warmup", "1", "--log-every", "1",
              "--seed", "0"]
SERVE_ARGS = ["--arch", "qwen1.5-4b", "--engine", "paged",
              "--requests", "8", "--batch", "8", "--prompt-len", "64",
              "--gen", "16", "--prefill-chunk", "32", "--seed", "0"]
# Rank 256, not the width rule's 512: at r = 512 the 2560-wide attention
# leaves pass neither sharding gate on four chips (n/4 = m/4 = 640 < 2r),
# stay replicated with their moments, and the step needs 20.3 GiB per
# device (compile for a described v5e 2x2 host); at r = 256 every
# low-rank leaf shards and it needs 9.9 GiB.
FOUR_CHIP_ARGS = ["--arch", "qwen1.5-4b", "--optimizer", "subtrack",
                  "--rank", "256", "--use-kernels", "--mesh", "host",
                  "--steps", "4", "--update-interval", "2", "--batch", "8",
                  "--seq", "256", "--warmup", "1", "--log-every", "1",
                  "--seed", "0"]


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"[chip_smoke] ok: {what}", flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    jax.monitoring events."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses

    def report(self, since: tuple[float, int, int], what: str) -> None:
        s, h, m = since
        print(f"[chip_smoke] {what}: backend compile {self.seconds - s:.1f}s,"
              f" persistent cache {self.hits - h} hits / "
              f"{self.misses - m} misses", flush=True)


def device_memory(jax, peaks: dict) -> None:
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak is None:
            print(f"[chip_smoke] device {d.id}: peak_bytes_in_use not "
                  "reported", flush=True)
            continue
        print(f"[chip_smoke] device {d.id}: peak_bytes_in_use {peak} "
              f"({peak / 2**30:.2f} GiB, {100 * peak / peaks['hbm_bytes']:.1f}"
              "% of HBM)", flush=True)


def free_device_memory(jax) -> None:
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    print(f"[chip_smoke] live device arrays after phase: {live} bytes",
          flush=True)


def run_train(jax, meter, name: str, argv: list[str],
              kernels: bool) -> dict:
    from repro.launch.train import train

    print(f"[chip_smoke] --- trainer: {name} ---", flush=True)
    since = meter.snapshot()
    t0 = time.time()
    summary = train(argv)
    wall = time.time() - t0
    meter.report(since, f"trainer {name}")
    hist = summary["history"]
    losses = [h["loss"] for h in hist]
    dts = [h.get("dt") for h in hist]
    print(f"[chip_smoke] {name}: losses {losses}", flush=True)
    print(f"[chip_smoke] {name}: step wall times (smoke reading, not a "
          f"benchmark; the first plain and first tracking step include "
          f"compilation) {[round(d, 4) for d in dts]}; run wall "
          f"{wall:.1f}s", flush=True)
    check(summary["backend"] == "tpu", f"{name}: backend is tpu")
    check(len(losses) == 6 and all(x is not None and math.isfinite(x)
                                   for x in losses),
          f"{name}: all 6 losses finite")
    check(not summary["quarantined_steps"] and summary["rollbacks"] == 0
          and not summary["skipped_batches"],
          f"{name}: no quarantined step, rollback or skipped batch")
    check(any(h.get("subspace_update") for h in hist),
          f"{name}: a tracking step ran")
    if kernels:
        check(summary["kernel_mode"] == "compiled",
              f"{name}: kernels dispatched compiled")
        paths = summary["kernel_paths"]
        for rec in paths:
            print(f"[chip_smoke] {name}: {rec['entry']} {rec['path']} "
                  f"{rec['shapes']}", flush=True)
        check(any(r["path"] == "kernel" for r in paths),
              f"{name}: fused kernels were traced")
        check(not any(r["path"] == "reference" for r in paths),
              f"{name}: no reference fallback")
    device_memory(jax, PEAKS[jax.devices()[0].device_kind])
    return summary


def trainer_phase(jax, meter) -> None:
    runs = {
        "kernels+grad-fused": (TRAIN_ARGS + ["--use-kernels", "--grad-fused"],
                               True),
        "kernels": (TRAIN_ARGS + ["--use-kernels"], True),
        "reference": (TRAIN_ARGS, False),
    }
    losses = {}
    for name, (argv, kernels) in runs.items():
        summary = run_train(jax, meter, name, argv, kernels)
        losses[name] = [h["loss"] for h in summary["history"]]
        del summary
        free_device_memory(jax)
    ref = losses["reference"]
    for name in ("kernels+grad-fused", "kernels"):
        diff = max(abs(a - b) for a, b in zip(losses[name], ref))
        print(f"[chip_smoke] {name} vs reference: max |loss diff| {diff:.6g}"
              f" (tolerance {LOSS_TOL})", flush=True)
        check(diff <= LOSS_TOL,
              f"{name} loss history agrees with the reference path")


def serving_phase(jax, meter) -> None:
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.launch.serve import serve

    print("[chip_smoke] --- server: qwen1.5-4b paged ---", flush=True)
    ops.reset_dispatch_tally()
    since = meter.snapshot()
    t0 = time.time()
    out = serve(SERVE_ARGS)
    meter.report(since, "server")
    print(f"[chip_smoke] server: {out['requests']} requests, "
          f"{out['tokens']} tokens, {out['decode_calls']} decode calls, "
          f"wall {time.time() - t0:.1f}s (smoke reading, includes "
          "compilation)", flush=True)
    check(out["engine"] == "paged", "server: the paged engine ran")
    check(out["requests"] == 8 and not out["shed"] and not out["expired"]
          and all(len(t) == 16 for t in out["outputs"].values()),
          "server: all 8 requests finished with 16 tokens each")
    tally = ops.dispatch_tally()
    paged = {k: v for k, v in tally.items() if k[0] == "paged_attention"}
    for (entry, path, shapes), n in paged.items():
        print(f"[chip_smoke] server: {entry} {path} {list(shapes)} "
              f"({n} traces)", flush=True)
    check(bool(paged) and all(k[1] == "kernel" for k in paged),
          "server: paged attention dispatched the compiled kernel")

    # the kernel against its oracle at the served decode shapes
    _, _, (q_shape, pool_shape) = next(iter(paged))
    B, Hq, hd = q_shape
    nb, bs, Hkv, _ = pool_shape
    W = (64 + 16 + bs - 1) // bs
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kl, kt = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, Hq, hd), jnp.bfloat16)
    k_pool = jax.random.normal(kk, pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(kv, pool_shape, jnp.bfloat16)
    lengths = jax.random.randint(kl, (B,), 1, W * bs + 1).astype(jnp.int32)
    tables = (1 + jax.random.permutation(kt, nb - 1)[:B * W]
              ).reshape(B, W).astype(jnp.int32)
    got = jax.jit(ops.paged_attention)(q, k_pool, v_pool, tables, lengths)
    want = jax.jit(ref.paged_attention_ref)(q, k_pool, v_pool, tables,
                                            lengths)
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    print(f"[chip_smoke] paged kernel vs oracle at q {q_shape}, pool "
          f"{pool_shape}: max |diff| / max |ref| = {err:.3g} (tolerance "
          f"{PAGED_TOL})", flush=True)
    check(err <= PAGED_TOL, "paged kernel matches paged_attention_ref")
    device_memory(jax, PEAKS[jax.devices()[0].device_kind])


def four_chip_phase(jax, meter) -> None:
    check(len(jax.devices()) == 4, "four devices visible")
    runs = {"hot path auto": FOUR_CHIP_ARGS + ["--hotpath-layout", "auto"],
            "hot path off": FOUR_CHIP_ARGS + ["--hotpath-layout", "off"]}
    for name, argv in runs.items():
        from repro.launch.train import train

        print(f"[chip_smoke] --- four chips: {name} ---", flush=True)
        since = meter.snapshot()
        summary = train(argv)
        meter.report(since, f"four chips {name}")
        losses = [h["loss"] for h in summary["history"]]
        print(f"[chip_smoke] {name}: losses {losses}; step wall times "
              "(smoke reading) "
              f"{[round(h['dt'], 4) for h in summary['history']]}",
              flush=True)
        regimes: dict[str, int] = {}
        for prog in summary["leaf_programs"].values():
            regimes[prog["regime"]] = regimes.get(prog["regime"], 0) + 1
        print(f"[chip_smoke] {name}: leaf regimes {regimes}", flush=True)
        check(summary["mesh_devices"] == 4, f"{name}: ran on 4 devices")
        check(all(x is not None and math.isfinite(x) for x in losses),
              f"{name}: all losses finite")
        check(not summary["quarantined_steps"]
              and summary["rollbacks"] == 0,
              f"{name}: no quarantined step or rollback")
        if name == "hot path auto":
            check(any(r != "replicated" for r in regimes),
                  f"{name}: sharded StepPrograms ran")
        device_memory(jax, PEAKS[jax.devices()[0].device_kind])
        del summary
        free_device_memory(jax)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"[chip_smoke] FAIL: no TPU (jax sees {platform}); this check "
              "runs only on the chip", file=sys.stderr, flush=True)
        return 2
    kind = devices[0].device_kind
    if kind not in PEAKS:
        print(f"[chip_smoke] FAIL: unknown device kind {kind!r}: no "
              "published peaks recorded for it", file=sys.stderr,
              flush=True)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[chip_smoke] FAIL: the repro package is not next to this "
              f"script ({e})", file=sys.stderr, flush=True)
        return 2
    print(f"[chip_smoke] {len(devices)} x {kind}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    meter = CompileMeter(jax.monitoring)
    try:
        if args.four_chips:
            four_chip_phase(jax, meter)
        else:
            check(len(devices) == 1, "one device visible")
            trainer_phase(jax, meter)
            serving_phase(jax, meter)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
