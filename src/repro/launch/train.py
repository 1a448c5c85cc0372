"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama-100m --optimizer subtrack --steps 300 \
        --batch 8 --seq 256 --checkpoint-dir /tmp/ckpt

Production behaviours exercised here (and tested in tests/test_train_loop.py):

* **checkpoint/restart**: async checkpoints every N steps; on start the
  loop restores the latest complete checkpoint and resumes from its step —
  the data pipeline is stateless-indexable so the token stream continues
  bit-exactly.
* **failure injection**: ``--fail-at-step K`` raises mid-run to prove the
  restart path (the integration test runs fail -> restart -> compare
  against an uninterrupted run).
* **straggler watchdog**: per-step wall time EMA/variance; steps slower
  than mu + 6 sigma are logged with host/process info — on a real fleet
  this is the hook the cluster manager consumes for hot-spare swaps.
* **subspace-update cadence**: the host picks the plain or tracking
  train-step variant per step (k from the optimizer config), mirroring
  Alg. 1's ``t mod k`` branch without bloating the hot compiled program.
* **warm start**: S_0 initialized from the first batch's gradients
  (Alg. 1 line 1) — skipped automatically on resume.
* **pipelined host loop**: the next batch is assembled while the device
  computes the current step, and the blocking ``float(metrics)`` drain
  trails dispatch by one step, so host work never serializes the device
  queue (divergence detection runs one step late by design).
* **self-healing runtime**: every step emits an in-graph
  ``HealthReport`` (repro.core.health) and quarantines itself under
  ``lax.cond`` when non-finite — params/M/V/S/count bit-identical, like
  a loss-scaling skip.  The host-side :class:`HealthSentinel` folds the
  device verdict, non-finite grad norms, and an EMA loss-spike gate into
  one escalation ladder: skip -> forced subspace refresh -> rollback to
  the newest *known-good* checkpoint with lr backoff -> abort.
  ``--inject kind@step`` (nan-grad, loss-spike, sigma-blowup,
  corrupt-batch, ckpt-io-error) exercises every rung; injections are
  consumed once so post-rollback replay is clean.
* **mesh-native hot path**: on a multi-device mesh with ``--use-kernels``
  each low-rank leaf is sharded in its cheapest admissible regime —
  column (n) or row (m), picked by the modeled per-device bytes
  (``hotpath_param_specs``; override with ``--hotpath-layout``) — and
  the fused optimizer step runs under ``shard_map`` — see
  repro.core.subtrack for the per-regime collective contract.
* **elastic mesh failover**: a step deadline on the metric drain (plus
  any raising collective) turns a hung/lost device into a ``MESH_LOST``
  verdict — distinct from the numerical ladder, because the *logical*
  state is fine and only the topology is suspect.  The runtime then
  rebuilds the mesh from the survivors (``degraded_context``), re-runs
  ``hotpath_param_specs`` + ``build_program`` on the new topology
  (regimes legitimately flip as group sizes shrink), elastic-restores
  the newest known-good checkpoint onto the re-planned programs via
  ``CheckpointManager.rollback``, and resumes — bounded by
  ``--max-failovers``.  ``--inject dev-loss@N`` simulates the loss on
  the fake mesh (raise or hang flavour), ``slow-host@N`` injects a
  stall that must trip the straggler watchdog without corrupting state.
* **preemption**: SIGTERM/SIGINT finishes the in-flight step, writes a
  blocking known-good checkpoint plus a ``RESUME`` marker, and exits 0;
  the restarted run consumes the marker and auto-resumes.  ``--inject
  preempt@N`` self-delivers the signal for the e2e tests.
* **profiling**: ``--profile-dir DIR --profile-steps A:B`` writes one
  profiler trace of steps A..B-1 in which the loop's host spans (a step
  annotation per step; ``fetch``, ``dispatch``, ``drain``, ``checkpoint``,
  ``sentinel``) and the device's ops share one clock; the compiled step
  names its layers with named scopes (``repro.launch.steps``,
  ``repro.models.transformer``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import signal
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.registry import PAPER_RANKS, get_config
from repro.core import health as health_lib
from repro.core.api import get_optimizer
from repro.core.program import StateDescriptor
from repro.data.pipeline import (DataConfig, SyntheticLMDataset,
                                 batch_for_model, corrupt_tokens, fetch_batch)
from repro.distributed import sharding as sh
from repro.distributed.context import mesh_context
from repro.kernels import ops as kernel_ops
from repro.launch.mesh import (MeshLostError, SimulatedDeviceLoss,
                               degraded_context, host_context,
                               is_device_loss, make_context, smoke_context)
from repro.checkpoint import transpose as ckpt_transpose
from repro.launch.steps import (TrainState, checkpoint_descriptors,
                                default_rank, make_train_step,
                                make_warm_start, train_state_shardings)
from repro.models.api import build_model
from repro.optim.schedules import cosine_with_warmup


class StragglerWatchdog:
    """Per-step wall-time anomaly detector (EMA mean/var, 6-sigma gate)."""

    def __init__(self, alpha: float = 0.05, warmup: int = 5,
                 sigma: float = 6.0):
        self.alpha, self.warmup, self.sigma = alpha, warmup, sigma
        self.mean = 0.0
        self.var = 0.0
        self.n = 0
        self.flagged: list[tuple[int, float]] = []

    def observe(self, step: int, dt: float) -> bool:
        self.n += 1
        if self.n <= self.warmup:
            self.mean = dt if self.n == 1 else \
                (self.mean * (self.n - 1) + dt) / self.n
            return False
        thresh = self.mean + self.sigma * math.sqrt(max(self.var, 1e-12))
        slow = dt > thresh and dt > self.mean * 1.5
        d = dt - self.mean
        self.mean += self.alpha * d
        self.var = (1 - self.alpha) * (self.var + self.alpha * d * d)
        if slow:
            self.flagged.append((step, dt))
            print(f"[watchdog] step {step} took {dt:.3f}s "
                  f"(mean {self.mean:.3f}s) — straggler suspected; "
                  f"host=0 process={jax.process_index()}", flush=True)
        return slow


class HealthSentinel:
    """Host-side health gate driving the escalation ladder.

    One verdict per drained step, from three strike sources folded into
    the same counter (the old host check only looked at the loss and let
    a non-finite grad norm with a finite loss sail through):

    * the device's in-graph quarantine verdict (``quarantined`` metric),
    * a non-finite drained loss OR grad norm,
    * an EMA loss-spike gate (same mean/var recursion as the straggler
      watchdog): loss > mean + sigma*sqrt(var) AND loss > mean*factor —
      this catches the finite-but-wrecked-model case quarantine cannot.

    Consecutive strikes climb the ladder: 1 -> skip (in-graph quarantine
    already protected the state; just log), 2 -> force a subspace
    refresh on the next dispatch (a poisoned S recovers from fresh
    gradients), >=3 -> roll back to the newest known-good checkpoint
    with lr backoff for a cooldown window.  A healthy step resets the
    counter; more than ``max_rollbacks`` rollbacks (or no known-good
    checkpoint when one is needed) aborts the run.

    Infrastructure faults take a separate door: :meth:`mesh_lost` is the
    ``MESH_LOST`` verdict for a hung or raising collective / lost device.
    It never touches the strike counter — the logical state is not
    suspect, the *topology* is — and escalates straight to ``FAILOVER``
    (rebuild the mesh from survivors, re-plan the StepPrograms, elastic-
    restore the newest known-good checkpoint; see the failover loop in
    :func:`train`).  No lr backoff either: the model was healthy.
    """

    OK, SKIP, REFRESH, ROLLBACK, ABORT = \
        "ok", "skip", "refresh", "rollback", "abort"
    MESH_LOST, FAILOVER = "mesh-lost", "failover"

    def __init__(self, alpha: float = 0.05, warmup: int = 5,
                 sigma: float = 4.0, factor: float = 1.25,
                 strikes_to_rollback: int = 3, max_rollbacks: int = 2,
                 lr_backoff: float = 0.5, cooldown: int = 10):
        self.alpha, self.warmup, self.sigma, self.factor = \
            alpha, warmup, sigma, factor
        self.strikes_to_rollback = strikes_to_rollback
        self.max_rollbacks = max_rollbacks
        self.lr_backoff = lr_backoff
        self.cooldown = cooldown
        self.mean = 0.0
        self.var = 0.0
        self.n = 0
        self.strikes = 0
        self.rollbacks = 0
        self.backoff_until = -1
        self.quarantined_steps: list[int] = []
        self.events: list[dict] = []

    def lr_scale(self, step: int) -> float:
        return self.lr_backoff if step < self.backoff_until else 1.0

    def _spiked(self, loss: float) -> bool:
        if self.n < self.warmup:
            return False
        thresh = self.mean + self.sigma * math.sqrt(max(self.var, 1e-12))
        return loss > thresh and loss > self.mean * self.factor

    def observe(self, step: int, loss: float, grad_norm: float,
                quarantined: bool) -> str:
        if quarantined:
            self.quarantined_steps.append(step)
            return self.strike(step, "step quarantined in-graph")
        if not (np.isfinite(loss) and np.isfinite(grad_norm)):
            return self.strike(
                step, f"non-finite drain (loss={loss}, gnorm={grad_norm})")
        if self._spiked(loss):
            return self.strike(
                step, f"loss spike ({loss:.4f} vs EMA {self.mean:.4f})")
        self.n += 1
        if self.n <= self.warmup:
            self.mean = loss if self.n == 1 else \
                (self.mean * (self.n - 1) + loss) / self.n
        else:
            d = loss - self.mean
            self.mean += self.alpha * d
            self.var = (1 - self.alpha) * (self.var + self.alpha * d * d)
        self.strikes = 0
        return self.OK

    def strike(self, step: int, reason: str) -> str:
        self.strikes += 1
        if self.strikes == 1:
            action = self.SKIP
        elif self.strikes < self.strikes_to_rollback:
            action = self.REFRESH
        else:
            self.strikes = 0
            self.rollbacks += 1
            action = (self.ABORT if self.rollbacks > self.max_rollbacks
                      else self.ROLLBACK)
        self.events.append({"step": step, "reason": reason,
                            "action": action})
        print(f"[sentinel] step {step}: {reason} — "
              f"strike -> {action}", flush=True)
        return action

    def note_rollback(self, resume_step: int) -> None:
        self.backoff_until = resume_step + self.cooldown

    def mesh_lost(self, step: int, reason: str) -> str:
        """The infrastructure verdict: record it and escalate straight to
        failover (no strikes, no lr backoff — see the class docstring)."""
        self.events.append({"step": step, "reason": reason,
                            "action": self.FAILOVER,
                            "verdict": self.MESH_LOST})
        print(f"[sentinel] step {step}: {reason} — verdict "
              f"{self.MESH_LOST} -> {self.FAILOVER}", flush=True)
        return self.FAILOVER


INJECT_KINDS = ("nan-grad", "loss-spike", "sigma-blowup", "corrupt-batch",
                "ckpt-io-error", "dev-loss", "preempt", "slow-host")

# Static eta multiplier for --inject sigma-blowup: with the default
# eta=10 this drives eta*sigma far past pi/2 on the injected tracking
# step, so the theta clamp (repro.core.health.THETA_MAX) must hold.
BLOWUP_ETA_SCALE = 1e6


class StepProfiler:
    """``--profile-dir``: one profiler trace of steps ``[first, stop)``.
    Each loop iteration is a step annotation and each host phase a span
    inside it, on the clock of the device's ops.  Without a directory no
    method calls the profiler."""

    def __init__(self, log_dir: str, steps: tuple[int, int]):
        self.log_dir = log_dir
        self.first, self.stop = steps
        self.tracing = self.done = False
        self._step = None

    def span(self, name: str):
        if not self.log_dir:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def begin_step(self, step: int | None, pending=None) -> None:
        """End the previous step's annotation and begin ``step``'s (None
        ends only).  The trace starts before the first step in range and
        stops before the first one past it, once ``pending`` (the outputs
        of the last step dispatched) are computed."""
        if not self.log_dir:
            return
        if self._step is not None:
            self._step.__exit__(None, None, None)
            self._step = None
        if step is None:
            return
        if self.tracing and step >= self.stop:
            if pending is not None:
                jax.block_until_ready(pending)
            self.close()
        if not (self.tracing or self.done) \
                and self.first <= step < self.stop:
            jax.profiler.start_trace(self.log_dir)
            self.tracing = True
        self._step = jax.profiler.StepTraceAnnotation("train", step_num=step)
        self._step.__enter__()

    def close(self) -> None:
        self.begin_step(None)
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing, self.done = False, True


def _step_range(spec: str) -> tuple[int, int]:
    first, _, stop = spec.partition(":")
    try:
        first, stop = int(first), int(stop)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected A:B (steps A..B-1), got {spec!r}") from None
    if not 0 <= first < stop:
        raise argparse.ArgumentTypeError(
            f"expected 0 <= A < B, got {spec!r}")
    return first, stop


def parse_injections(spec: str) -> dict[int, str]:
    """``kind@step[,kind@step...]`` -> {step: kind}."""
    out: dict[int, str] = {}
    if not spec:
        return out
    for part in spec.split(","):
        kind, _, at = part.strip().rpartition("@")
        if kind not in INJECT_KINDS:
            raise SystemExit(
                f"--inject: unknown kind {kind!r} (choose from "
                f"{', '.join(INJECT_KINDS)})")
        out[int(at)] = kind
    return out


def _parse_args(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama-100m")
    ap.add_argument("--optimizer", default="subtrack")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--update-interval", type=int, default=200)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=100)
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--mesh", default="smoke",
                    choices=["smoke", "host", "prod", "multipod"],
                    help="smoke: 1 device; host: (1, N) over all local "
                         "devices (fake-multi-device fault-injection "
                         "runs); prod/multipod: production topologies")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", default="elastic",
                    choices=["elastic", "strict", "off"],
                    help="checkpoint resume mode: elastic (default) "
                         "rebuilds the StepProgram descriptors for the "
                         "CURRENT mesh/config and restores through the "
                         "layout-transposing pass (repro.checkpoint."
                         "transpose) — a checkpoint written under any "
                         "regime/group size/rank restores here; strict "
                         "requires identical state shapes; off starts "
                         "fresh (checkpoints are still written)")
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="failure injection: raise at this step")
    ap.add_argument("--inject", default="",
                    help="fault injection: comma-separated kind@step with "
                         f"kind in {{{', '.join(INJECT_KINDS)}}} — e.g. "
                         "'nan-grad@13,loss-spike@31'.  Each entry fires "
                         "once (consumed), so replay after a sentinel "
                         "rollback is clean.  Infrastructure kinds: "
                         "dev-loss (a device subset leaves the mesh at "
                         "step N and STAYS lost until failover — see "
                         "--survivors/--dev-loss-mode), preempt (self-"
                         "delivered SIGTERM), slow-host (a --stall-s "
                         "stall that must trip the straggler watchdog "
                         "without corrupting state)")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="with --mesh host: build the (1, N) mesh over "
                         "only the first N local devices (0 = all) — how "
                         "the failover tests run uninjected degraded-mesh "
                         "reference trajectories")
    ap.add_argument("--step-timeout", type=float, default=300.0,
                    help="deadline (s) on each step's device compute / "
                         "metric drain; exceeding it is a MESH_LOST "
                         "verdict (a collective presumed hung) and "
                         "triggers failover.  0 disables")
    ap.add_argument("--survivors", type=int, default=0,
                    help="device count the mesh shrinks to on failover "
                         "when the fault does not name survivors "
                         "(0 = half the mesh, min 1); also the subset "
                         "size --inject dev-loss leaves alive")
    ap.add_argument("--dev-loss-mode", default="raise",
                    choices=["raise", "hang"],
                    help="--inject dev-loss flavour: raise surfaces a "
                         "failed collective at dispatch; hang blocks the "
                         "metric drain so the --step-timeout watchdog "
                         "must catch it")
    ap.add_argument("--hang-s", type=float, default=30.0,
                    help="how long the simulated hung collective blocks "
                         "(dev-loss hang mode; keep it above "
                         "--step-timeout so the deadline fires first)")
    ap.add_argument("--stall-s", type=float, default=0.75,
                    help="--inject slow-host stall duration (s)")
    ap.add_argument("--max-failovers", type=int, default=2,
                    help="mesh rebuilds allowed before a MESH_LOST "
                         "verdict is re-raised to the operator")
    ap.add_argument("--save-timeout", type=float, default=60.0,
                    help="bound (s) on checkpoint-save waits during "
                         "preemption drain and failover — a hung "
                         "filesystem must not hang the exit path")
    ap.add_argument("--resume-marker", default="on", choices=["on", "off"],
                    help="write a RESUME marker on preemption and consume "
                         "it (with a log line) on the next start; off "
                         "disables both sides")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--profile-dir", default="",
                    help="write a profiler trace of --profile-steps here: "
                         "the loop's host spans and the device's ops on "
                         "one clock (off when empty: no profiler calls)")
    ap.add_argument("--profile-steps", type=_step_range, default=(1, 4),
                    metavar="A:B",
                    help="the steps A..B-1 that --profile-dir traces")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eta", type=float, default=10.0)
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--grad-fused", action="store_true",
                    help="emit each taggable leaf's [A = S^T G; colnorms] "
                         "panel from the backward pass (custom-vjp matmul "
                         "tap) and let the optimizer's plain steps consume "
                         "it instead of re-reading the full-width gradient; "
                         "silently falls back for untaggable leaves "
                         "(embeddings, MoE/MLA blocks), model families "
                         "without taps, accum > 1, and tracking steps")
    ap.add_argument("--hotpath-layout", default="auto",
                    choices=["auto", "column", "row", "row-rs", "off"],
                    help="mesh-native fused-optimizer layout: auto picks "
                         "column or row sharding per leaf by the modeled "
                         "per-device bytes (repro.kernels.traffic); "
                         "column/row restrict to one regime (row still "
                         "auto-picks its Adam-state flavour); row-rs "
                         "forces the reduce-scatter row variant (M/V "
                         "sharded into n/g slices); off disables the "
                         "shard_map'd hot path (GSPMD propagation)")
    return ap.parse_args(argv)


class _FailoverSession:
    """Host state that must survive a mesh failover (each `_run` rebuilds
    everything mesh-derived from scratch; everything here carries over):
    the consumed-once injection table, the sentinel (its events and loss
    EMA are mesh-independent), accumulated history, the checkpoint
    manager, the armed device-loss simulator and the preemption flag."""

    def __init__(self, args: argparse.Namespace):
        self.injections = parse_injections(args.inject)
        self.inject_on = bool(self.injections)
        self.sentinel = HealthSentinel()
        self.watchdog = StragglerWatchdog()
        self.history: list[dict] = []
        self.skipped_batches: list[int] = []
        self.ckpt = (CheckpointManager(args.checkpoint_dir)
                     if args.checkpoint_dir else None)
        self.dev_loss = SimulatedDeviceLoss()
        self.preempt = False                 # set by the signal handler
        self.preempt_signum: int | None = None
        self.resume_via_rollback = False     # next _run restores via rollback
        self.failovers = 0
        self.failover_events: list[dict] = []
        self.prev_programs: list[tuple] | None = None
        self.profiler = StepProfiler(args.profile_dir, args.profile_steps)
        self.t_start = time.time()


def _install_preempt_handlers(session: _FailoverSession):
    """SIGTERM/SIGINT -> preemption drain (finish the in-flight step,
    blocking known-good save, RESUME marker, exit 0).  Handlers only
    install from the main thread (signal.signal's constraint); the
    previous handlers are returned so an in-process caller (pytest) gets
    them back afterwards."""
    if threading.current_thread() is not threading.main_thread():
        return None
    prev = {}
    def handler(signum, frame):
        session.preempt = True
        session.preempt_signum = signum
        print(f"[train] caught signal {signum} — preemption: finishing "
              "the in-flight step, saving known-good, exiting cleanly",
              flush=True)
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[sig] = signal.signal(sig, handler)
        except (ValueError, OSError):  # pragma: no cover - exotic platform
            pass
    return prev


def _restore_preempt_handlers(prev) -> None:
    for sig, h in (prev or {}).items():
        try:
            signal.signal(sig, h)
        except (ValueError, OSError):  # pragma: no cover
            pass


def _deadline(fn, timeout: float, what: str):
    """Run ``fn`` under a wall-clock deadline.  A device sync that never
    returns (hung collective, dead participant) becomes a
    :class:`MeshLostError` after ``timeout`` seconds — the runner thread
    cannot be cancelled and is abandoned (daemon), which is exactly the
    semantics of a host giving up on a wedged device.  ``timeout <= 0``
    runs inline."""
    if not timeout or timeout <= 0:
        return fn()
    box: dict = {}

    def run():
        try:
            box["ok"] = fn()
        except BaseException as e:  # re-raised on the caller's thread
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    if t.is_alive():
        raise MeshLostError(
            f"step deadline exceeded ({timeout:.1f}s) during {what} — "
            "device compute or a collective presumed hung")
    if "err" in box:
        raise box["err"]
    return box.get("ok")


def _failover(args, session: _FailoverSession, ctx, err: MeshLostError):
    """Handle a MESH_LOST verdict: pick the survivors, rebuild the mesh
    context, and flag the next ``_run`` to elastic-restore the newest
    known-good checkpoint onto the re-planned programs.  Re-raises when
    failover cannot help (no checkpoint dir, budget exhausted)."""
    step = err.step if err.step is not None else \
        (session.history[-1]["step"] if session.history else -1)
    session.sentinel.mesh_lost(step, str(err))
    session.failovers += 1
    if session.ckpt is None:
        raise MeshLostError(
            "mesh lost with no --checkpoint-dir: nothing to fail over "
            "from") from err
    if session.failovers > args.max_failovers:
        raise MeshLostError(
            f"mesh lost again after {args.max_failovers} failover(s) — "
            "giving up") from err
    # Absorb any in-flight save (bounded — a hung filesystem must not
    # also hang the failover); its error, if any, is not fatal here: the
    # rollback below targets already-landed known-good steps.
    try:
        session.ckpt.wait(timeout=args.save_timeout)
    except OSError as e:
        print(f"[failover] pending checkpoint save abandoned ({e})",
              flush=True)
    survivors = err.survivors
    if not survivors:
        keep = args.survivors or max(1, len(jax.devices()) // 2)
        survivors = jax.devices()[:keep]
    session.dev_loss.disarm()         # the lost devices are out of the mesh
    session.resume_via_rollback = True
    fresh = StragglerWatchdog()       # new topology, new timing statistics
    fresh.flagged = session.watchdog.flagged
    session.watchdog = fresh
    session.failover_events.append({
        "step": step, "from_devices": int(ctx.mesh.devices.size),
        "to_devices": len(survivors)})
    print(f"[failover] rebuilding mesh {ctx.mesh.devices.size} -> "
          f"{len(survivors)} devices; re-planning StepPrograms and "
          "elastic-restoring the newest known-good checkpoint", flush=True)
    return degraded_context(survivors)


def train(argv=None) -> dict:
    args = _parse_args(argv)
    kernel_ops.reset_dispatch_tally()   # kernel_paths cover this run only
    ctx = (smoke_context() if args.mesh == "smoke"
           else host_context(limit=args.mesh_devices or None)
           if args.mesh == "host"
           else make_context(multi_pod=args.mesh == "multipod"))
    session = _FailoverSession(args)
    prev_handlers = _install_preempt_handlers(session)
    try:
        while True:
            try:
                return _run(args, ctx, session)
            except MeshLostError as e:
                ctx = _failover(args, session, ctx, e)
            except jax.errors.JaxRuntimeError as e:
                # A real raising collective / dead backend surfaces here
                # (not via the simulator): same MESH_LOST door, bounded
                # by the same failover budget.  Any other runtime error
                # (out of memory, a faulting kernel) is the program's own
                # and propagates with its message.
                if not is_device_loss(e):
                    raise
                ctx = _failover(args, session, ctx, MeshLostError(
                    f"runtime error treated as mesh loss: {e}"))
    finally:
        session.profiler.close()
        _restore_preempt_handlers(prev_handlers)


def _run(args, ctx, session: _FailoverSession) -> dict:
    injections = session.injections
    inject_on = session.inject_on

    with mesh_context(ctx):
        cfg = get_config(args.arch, smoke=args.smoke)
        bundle = build_model(cfg)
        rank = args.rank or PAPER_RANKS.get(args.arch,
                                            default_rank(cfg.d_model))
        opt_kw: dict = {}
        hot_specs = None
        if args.optimizer not in ("adamw", "badam"):
            opt_kw = dict(rank=rank, update_interval=args.update_interval,
                          eta=args.eta, weight_decay=args.weight_decay,
                          use_kernels=args.use_kernels)
            if args.use_kernels and ctx.mesh.devices.size > 1 \
                    and args.hotpath_layout == "off":
                # GSPMD cannot partition a compiled Pallas kernel; without
                # the shard_map'd hot path the optimizer runs its jnp path
                print("[train] --hotpath-layout off on a "
                      f"{ctx.mesh.devices.size}-device mesh: the optimizer "
                      "runs the reference (jnp) path — Pallas kernels run "
                      "only inside the hot path's shard_map", flush=True)
                opt_kw["use_kernels"] = False
            if args.use_kernels and ctx.mesh.devices.size > 1 \
                    and args.hotpath_layout != "off":
                # mesh-native fused hot path: shard every low-rank leaf
                # in its cheapest admissible regime and run the
                # per-matrix step through its StepProgram (see
                # repro.core.program for the regime x collective table);
                # --hotpath-layout row-rs additionally forces the
                # reduce-scatter Adam-state flavour in the optimizer
                # config (otherwise row leaves auto-pick by bytes)
                regimes = (("column", "row")
                           if args.hotpath_layout == "auto"
                           else (args.hotpath_layout,))
                row_state = ("reduce-scatter"
                             if args.hotpath_layout == "row-rs" else "auto")
                if args.hotpath_layout == "row-rs":
                    opt_kw.update(row_state=row_state)
                shapes = jax.eval_shape(bundle.init,
                                        jax.random.PRNGKey(args.seed))
                hot_specs = sh.hotpath_param_specs(shapes, ctx, rank,
                                                   regimes=regimes,
                                                   row_state=row_state)
                opt_kw.update(mesh=ctx.mesh, param_specs=hot_specs)
        elif args.weight_decay:
            opt_kw = dict(weight_decay=args.weight_decay)
        optimizer = get_optimizer(args.optimizer, **opt_kw)
        if opt_kw.get("use_kernels"):
            mode = (f"mesh-sharded (shard_map, regime-aware layout: "
                    f"{args.hotpath_layout})"
                    if "mesh" in opt_kw else "single-device")
            print("[train] optimizer hot path: fused single-pass kernels "
                  f"[{mode}] "
                  "(project_colnorms -> adam_lowrank_norms -> fused_update)",
                  flush=True)

        data = SyntheticLMDataset(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq,
            global_batch=args.batch, seed=args.seed))
        sched = cosine_with_warmup(args.lr, args.steps, args.warmup)

        key = jax.random.PRNGKey(args.seed)
        params = bundle.init(key)
        hot_shardings = (sh.to_named(hot_specs, ctx)
                         if hot_specs is not None else None)
        if hot_shardings is not None:
            # the optimizer's shard_map in/out specs assume this layout;
            # placing params (and pinning grads to the SAME shardings)
            # means GSPMD never reshards around the hot path — the two
            # documented psums stay the step's only collectives
            params = jax.device_put(params, hot_shardings)
        elif ctx.mesh.devices.size > 1:
            # no hot-path layout: the production TP/FSDP rules spread the
            # parameters over the mesh (GSPMD propagates the rest) instead
            # of leaving every one of them on the first device
            params = jax.device_put(params, sh.to_named(
                sh.param_specs(params, ctx), ctx))
        state = TrainState(params=params, opt=optimizer.init(params))
        leaf_programs = {}
        if ctx.mesh.devices.size > 1 \
                and args.optimizer not in ("adamw", "badam"):
            # which StepProgram regime each low-rank leaf runs on this mesh
            # (replicated everywhere when the hot path is off)
            descs = checkpoint_descriptors(
                state.params, optimizer,
                mesh=ctx.mesh if hot_specs is not None else None,
                param_specs=hot_specs)
            for kp, d in jax.tree_util.tree_flatten_with_path(
                    descs, is_leaf=lambda x: isinstance(x, StateDescriptor)
            )[0]:
                if d.kind == "lowrank":
                    leaf_programs[jax.tree_util.keystr(kp)] = {
                        "regime": d.regime, "shards": int(d.shards),
                        "state_layout": d.state_layout}
            for path, prog in leaf_programs.items():
                print(f"[train] leaf {path}: {prog['regime']} x"
                      f"{prog['shards']} (state {prog['state_layout']})",
                      flush=True)

        grad_fused = bool(args.grad_fused)
        if grad_fused and args.optimizer in ("adamw", "badam"):
            grad_fused = False  # dense baselines have no projection to tap
        if grad_fused and (bundle.loss_taps is None or args.accum > 1):
            print("[train] --grad-fused requested but "
                  + ("this model family exposes no taggable matmuls"
                     if bundle.loss_taps is None
                     else "gradient accumulation is on (taps are not "
                          "additive across microbatches)")
                  + " — falling back to the plain backward", flush=True)
            grad_fused = False
        if grad_fused:
            print("[train] grad-fused backward: taggable leaves emit "
                  "[A; colnorms] from the weight-cotangent epilogue; "
                  "plain optimizer steps skip their projection read of G",
                  flush=True)
        train_step = make_train_step(
            bundle, optimizer, accum=args.accum, remat=args.remat,
            grad_shardings=hot_shardings, grad_fused=grad_fused,
            inject=inject_on)
        static = (("do_subspace_update", "eta_scale") if inject_on
                  else ("do_subspace_update",))
        jit_step = jax.jit(train_step, static_argnames=static,
                           donate_argnums=(0,))
        warm = jax.jit(make_warm_start(bundle, optimizer, remat=args.remat),
                       donate_argnums=(0,))

        ckpt = session.ckpt
        start_step = 0
        ckpt_extra: dict = {}
        restore_shardings = restore_loader = None
        if ckpt is not None:
            # the per-leaf StepProgram descriptors of THIS run's layouts:
            # embedded in every save (the source programs a later restore
            # transposes from) and, on restore, the transpose targets
            descs = checkpoint_descriptors(
                state.params, optimizer,
                mesh=ctx.mesh if hot_specs is not None else None,
                param_specs=hot_specs)
            ckpt_extra = ckpt_transpose.state_program_records(state, descs)
            # the elastic restore pieces double as the sentinel's rollback
            # path — a rollback IS an in-process elastic restore
            restore_shardings = train_state_shardings(
                state, descs,
                ctx.mesh if hot_shardings is not None else None,
                hot_shardings)
            restore_loader = ckpt_transpose.elastic_loader(descs)
            # re-planning ledger: after a failover the descriptors above
            # were rebuilt against the degraded mesh — diff them against
            # the pre-fault programs so the regime/group flips are
            # observable (summary + log), not just implicit
            progs = [(d.regime, int(d.shards), d.state_layout, int(d.rank))
                     for d in ckpt_transpose.descriptor_leaves(descs)
                     if d.kind == "lowrank"]
            if session.resume_via_rollback \
                    and session.prev_programs is not None:
                changed = sum(1 for a, b in
                              zip(session.prev_programs, progs) if a != b)
                if session.failover_events:
                    session.failover_events[-1]["program_changes"] = changed
                print(f"[failover] re-planned StepPrograms on the "
                      f"{ctx.mesh.devices.size}-device mesh: {changed} of "
                      f"{len(progs)} low-rank leaves changed "
                      "regime/group/state-layout", flush=True)
            session.prev_programs = progs
            if args.resume_marker == "on":
                marker = ckpt.consume_resume_marker()
                if marker:
                    print(f"[train] resume marker found "
                          f"(step {marker.get('step')}, "
                          f"{marker.get('reason')}) — auto-resuming",
                          flush=True)
            if session.resume_via_rollback:
                # failover resume: the newest KNOWN-GOOD checkpoint,
                # elastic-transposed onto the re-planned programs and
                # device_put with the degraded mesh's shardings — the
                # manager saved under the old mesh's layouts, restores
                # under the new ones
                res = ckpt.rollback(state, shardings=restore_shardings,
                                    loader=restore_loader)
                if res is None:
                    raise RuntimeError(
                        "[failover] unrecoverable: mesh lost but no "
                        "known-good checkpoint restores onto the "
                        "degraded mesh")
                state, ck_step = res
                start_step = ck_step + 1
                session.resume_via_rollback = False
                if session.failover_events:
                    session.failover_events[-1]["restored_step"] = ck_step
                    session.failover_events[-1]["resume_step"] = start_step
                print(f"[failover] restored known-good step {ck_step} "
                      f"onto {ctx.mesh.devices.size} devices; resuming "
                      f"at step {start_step}", flush=True)
            elif args.resume != "off":
                if args.resume == "elastic":
                    restored = ckpt.restore(state,
                                            shardings=restore_shardings,
                                            loader=restore_loader)
                else:
                    restored = ckpt.restore(state)
                if restored is not None:
                    state, start_step = restored
                    start_step += 1
                    print(f"[train] resumed from checkpoint step "
                          f"{start_step - 1} ({args.resume} restore)",
                          flush=True)

        k = getattr(optimizer.config, "update_interval", 0)
        baseline = args.optimizer in ("adamw", "badam")
        watchdog = session.watchdog
        sentinel = session.sentinel
        prof = session.profiler
        history = session.history
        skipped_batches = session.skipped_batches
        dev_loss = session.dev_loss

        if start_step == 0 and not baseline:
            batch0 = batch_for_model(cfg, None, data, 0)
            state, warm_loss = warm(state, batch0)
            print(f"[train] warm-started subspaces from step-0 gradients "
                  f"(loss {float(warm_loss):.4f})", flush=True)

        # Pipelined host loop: dispatch step t, prefetch batch t+1 while
        # the device computes, and only then drain step t-1's metrics —
        # the blocking float(...) sync always trails the dispatch frontier
        # by one step, so the host keeps the device queue non-empty
        # instead of serializing dispatch -> compute -> readback every
        # step.  Consequence (documented): the sentinel sees step t's
        # health one step late — in-graph quarantine already protected
        # the state, so the late verdict only drives *escalation* (the
        # ladder), never correctness.  On rollback the just-dispatched
        # step is discarded undrained and the loop rewinds to the
        # checkpoint's step; the stateless data pipeline makes the rewind
        # a pure counter reset.

        def drain(rec: dict, metrics) -> str:
            # The blocking device sync runs under the step deadline: a
            # hung collective (or the armed dev-loss simulator) becomes
            # MESH_LOST instead of wedging the host forever.  Once the
            # sync returns, the float() reads below are host-local.
            def sync():
                dev_loss.check(rec["step"], "drain")
                jax.block_until_ready(metrics["loss"])
            with prof.span("drain"):
                try:
                    _deadline(sync, args.step_timeout,
                              f"metric drain of step {rec['step']}")
                except MeshLostError as e:
                    if e.step is None:
                        e.step = rec["step"]
                    raise
                loss = float(metrics["loss"])      # blocks on rec["step"]
                rec["loss"] = loss
                rec["grad_norm"] = float(metrics["grad_norm"])
                rec["quarantined"] = bool(float(metrics["quarantined"]))
                rec["theta_clamped"] = bool(float(metrics["theta_clamped"]))
            rec["dt"] = time.time() - rec.pop("t0")
            watchdog.observe(rec["step"], rec["dt"])
            history.append(rec)
            if rec["step"] % args.log_every == 0 \
                    or rec["step"] == args.steps - 1:
                print(f"[train] step {rec['step']:5d}  loss {loss:8.4f}  "
                      f"lr {rec['lr']:.2e}  {rec['dt']:6.2f}s"
                      f"{'  [subspace update]' if rec['subspace_update'] else ''}"
                      f"{'  [QUARANTINED]' if rec['quarantined'] else ''}",
                      flush=True)
            with prof.span("sentinel"):
                return sentinel.observe(rec["step"], loss, rec["grad_norm"],
                                        rec["quarantined"])

        def fetch(s: int):
            """Resilient (retry + validate) prefetch of global batch s."""
            if s >= args.steps:
                return None, True
            mut = None
            if injections.get(s) == "corrupt-batch":
                injections.pop(s)
                mut = corrupt_tokens
            return fetch_batch(cfg, data, s, mutate=mut)

        pending_refresh = False

        def apply_action(act: str, at_step: int, cur_state):
            """Execute a sentinel verdict.  Returns (state, resume_step)
            on rollback, None otherwise; raises on abort."""
            nonlocal pending_refresh
            if act in (HealthSentinel.OK, HealthSentinel.SKIP):
                return None
            if act == HealthSentinel.REFRESH:
                pending_refresh = True
                return None
            if act == HealthSentinel.ABORT:
                raise FloatingPointError(
                    f"[sentinel] aborting at step {at_step}: escalation "
                    f"ladder exhausted after {sentinel.max_rollbacks} "
                    "rollbacks")
            with prof.span("sentinel"):
                res = ckpt.rollback(cur_state, shardings=restore_shardings,
                                    loader=restore_loader) \
                    if ckpt is not None else None
            if res is None:
                raise FloatingPointError(
                    f"[sentinel] unrecoverable at step {at_step}: rollback "
                    "requested but no known-good checkpoint is available")
            tree, ck_step = res
            sentinel.note_rollback(resume_step=ck_step + 1)
            pending_refresh = False
            print(f"[sentinel] rolled back to known-good checkpoint step "
                  f"{ck_step}; resuming at {ck_step + 1} with lr x"
                  f"{sentinel.lr_backoff} for {sentinel.cooldown} steps",
                  flush=True)
            return tree, ck_step + 1

        inflight = None                            # (rec, metrics) of step-1
        last_act = HealthSentinel.OK
        step = start_step
        batch, batch_ok = fetch(step)
        stall_s = 0.0
        while True:
            while step < args.steps:
                prof.begin_step(step, None if inflight is None
                                else inflight[1])
                if session.preempt:
                    break                          # graceful drain below
                if step == args.fail_at_step:
                    if ckpt:
                        ckpt.wait()
                    raise RuntimeError(
                        f"[failure-injection] simulated node failure at step {step}")
                kind = injections.get(step)
                if kind is not None and kind != "corrupt-batch":
                    injections.pop(step)           # consumed-once
                else:
                    kind = None
                if kind == "ckpt-io-error":
                    if ckpt:
                        # flaky-filesystem injection: the next save's first
                        # attempts raise OSError; the bounded retry in
                        # CheckpointManager.save must absorb them
                        ckpt.fail_next_saves(2)
                    kind = None
                if kind == "dev-loss":
                    # arm the simulator: from this step on, the mesh has
                    # lost all but the survivor subset (stays armed until
                    # failover disarms it — a lost device stays lost)
                    keep = args.survivors \
                        or max(1, ctx.mesh.devices.size // 2)
                    survivors = list(ctx.mesh.devices.flat)[:keep]
                    dev_loss.arm(step, survivors, mode=args.dev_loss_mode,
                                 hang_s=args.hang_s)
                    print(f"[inject] step {step}: dev-loss "
                          f"({ctx.mesh.devices.size} -> {keep} devices, "
                          f"mode={args.dev_loss_mode})", flush=True)
                    kind = None
                if kind == "preempt":
                    # self-delivered SIGTERM: the handler sets the flag,
                    # the NEXT loop top takes the graceful-drain branch
                    # (this step still dispatches — "finish the in-flight
                    # step" semantics)
                    print(f"[inject] step {step}: preempt (SIGTERM to "
                          "self)", flush=True)
                    os.kill(os.getpid(), signal.SIGTERM)
                    kind = None
                if kind == "slow-host":
                    stall_s = args.stall_s         # applied after dispatch
                    print(f"[inject] step {step}: slow-host "
                          f"(+{stall_s:.2f}s stall)", flush=True)
                    kind = None
                if dev_loss.armed:
                    # raise-mode device loss surfaces at dispatch (XLA
                    # reports a dead participant on the calling thread)
                    dev_loss.check(step, "dispatch")
                if not batch_ok:
                    # skip-marked batch from the resilient fetch: one
                    # strike, no dispatch — the step is simply not taken
                    skipped_batches.append(step)
                    history.append({"step": step, "loss": None,
                                    "skipped_batch": True})
                    act = sentinel.strike(step,
                                          "unusable batch (skip-marked)")
                    rb = apply_action(act, step, state)
                    if rb is not None:
                        state, step = rb
                        inflight = None
                    else:
                        step += 1
                    batch, batch_ok = fetch(step)
                    continue
                t0 = time.time()
                do_update = bool(k) and step > 0 and step % k == 0 \
                    and not baseline
                if not baseline and (pending_refresh
                                     or kind == "sigma-blowup"):
                    if pending_refresh:
                        print(f"[sentinel] step {step}: forcing subspace "
                              "refresh", flush=True)
                    do_update = True
                pending_refresh = False
                lr = float(sched(step)) * sentinel.lr_scale(step)
                if inject_on:
                    if kind:
                        print(f"[inject] step {step}: {kind}", flush=True)
                    code = {None: health_lib.INJECT_NONE,
                            "nan-grad": health_lib.INJECT_NAN_GRAD,
                            "loss-spike": health_lib.INJECT_LOSS_SPIKE,
                            "sigma-blowup": health_lib.INJECT_NONE}[kind]
                    eta_scale = (BLOWUP_ETA_SCALE
                                 if kind == "sigma-blowup" else 1.0)
                    with prof.span("dispatch"):
                        state, metrics = jit_step(
                            state, batch, jnp.float32(lr), jnp.int32(code),
                            do_subspace_update=do_update,
                            eta_scale=eta_scale)
                else:
                    with prof.span("dispatch"):
                        state, metrics = jit_step(
                            state, batch, jnp.float32(lr),
                            do_subspace_update=do_update)
                if stall_s:
                    # slow-host injection: a pure host-side stall — the
                    # step's wall time inflates (the straggler watchdog
                    # must flag it at drain) but device state is untouched
                    time.sleep(stall_s)
                    stall_s = 0.0
                with prof.span("fetch"):             # prefetch under compute
                    nbatch, nbatch_ok = fetch(step + 1)
                act = HealthSentinel.OK
                if inflight is not None:
                    act = drain(*inflight)
                    last_act = act
                rb = apply_action(act, step - 1, state)
                if rb is not None:
                    # the just-dispatched step ran on suspect state —
                    # discard it undrained and rewind to the checkpoint
                    state, step = rb
                    inflight = None
                    batch, batch_ok = fetch(step)
                    continue
                inflight = ({"step": step, "lr": lr,
                             "subspace_update": do_update, "t0": t0},
                            metrics)
                batch, batch_ok = nbatch, nbatch_ok
                if ckpt and step and step % args.checkpoint_every == 0:
                    # validate THIS step's health before persisting —
                    # only a step the sentinel passes is tagged
                    # known-good (the rollback targets); a step that
                    # itself escalates is never saved at all
                    act = drain(*inflight)
                    last_act = act
                    inflight = None
                    rb = apply_action(act, step, state)
                    if rb is not None:
                        state, step = rb
                        batch, batch_ok = fetch(step)
                        continue
                    with prof.span("checkpoint"):
                        ckpt.save(step, state, extra_meta=ckpt_extra,
                                  known_good=(act == HealthSentinel.OK))
                step += 1
            prof.begin_step(None)
            if session.preempt:
                break
            if inflight is None:
                break
            act = drain(*inflight)
            last_act = act
            inflight = None
            rb = apply_action(act, args.steps - 1, state)
            if rb is None:
                break
            state, step = rb                       # tail rollback: re-enter
            batch, batch_ok = fetch(step)

        preempted = False
        if session.preempt:
            # Preemption drain: finish the in-flight step (it already
            # dispatched — drain its metrics so the save below is tagged
            # off an observed-healthy verdict), write a bounded blocking
            # known-good save plus the RESUME marker, and exit cleanly.
            # Every checkpoint wait is bounded: a hung filesystem must
            # not turn a preemption into a SIGKILL.
            act = HealthSentinel.OK
            if inflight is not None:
                act = drain(*inflight)
                inflight = None
            save_step = history[-1]["step"] if history \
                else max(start_step - 1, 0)
            if ckpt is not None:
                try:
                    ckpt.wait(timeout=args.save_timeout)
                    ckpt.save(save_step, state, extra_meta=ckpt_extra,
                              known_good=(act == HealthSentinel.OK))
                    ckpt.wait(timeout=args.save_timeout)
                except OSError as e:
                    print(f"[train] preemption save did not land ({e}) — "
                          "the previous checkpoint is the resume point",
                          flush=True)
                if args.resume_marker == "on":
                    ckpt.write_resume_marker(
                        save_step,
                        reason=f"preempted (signal "
                               f"{session.preempt_signum})")
            preempted = True
            print(f"[train] preemption drain complete at step {save_step}"
                  " — exiting cleanly for restart", flush=True)
        elif ckpt:
            with prof.span("checkpoint"):
                ckpt.save(args.steps - 1, state, blocking=True,
                          extra_meta=ckpt_extra,
                          known_good=(last_act == HealthSentinel.OK))

        wall = time.time() - session.t_start
        paths = [{"entry": e, "path": p, "shapes": [list(x) for x in shp],
                  "traces": n}
                 for (e, p, shp), n in kernel_ops.dispatch_tally().items()]
        for rec in paths:
            print(f"[kernels] {rec['entry']:26s} {rec['path']:9s} "
                  f"{rec['shapes']}", flush=True)
        summary = {
            "arch": cfg.name, "optimizer": args.optimizer, "rank": rank,
            "backend": jax.default_backend(),
            "kernel_mode": kernel_ops._mode(),
            "kernel_paths": paths,
            "leaf_programs": leaf_programs,
            "steps": args.steps, "final_loss": history[-1]["loss"]
            if history else None,
            "wall_time_s": wall,
            "state_bytes": optimizer.state_bytes(state.params),
            "stragglers": watchdog.flagged,
            "quarantined_steps": sentinel.quarantined_steps,
            "rollbacks": sentinel.rollbacks,
            "skipped_batches": skipped_batches,
            "sentinel_events": sentinel.events,
            "preempted": preempted,
            "failovers": session.failovers,
            "failover_events": session.failover_events,
            "mesh_devices": int(ctx.mesh.devices.size),
            "history": history,
        }
        if args.metrics_out:
            Path(args.metrics_out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.metrics_out).write_text(json.dumps(summary, indent=2))
        if not preempted:
            print(f"[train] done: {args.steps} steps in {wall:.1f}s, "
                  f"final loss {summary['final_loss']}", flush=True)
        return summary


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    train()
