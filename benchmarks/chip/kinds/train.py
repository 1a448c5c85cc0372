"""Timed loop of a training cell: SubTrack++ steps of the program's jitted
train step, fed by the program's seeded data pipeline.

Set-up builds one object, the jitted step with its state: weights from
the seed, the warm start (S_0 from the first gradient), and three plain
steps through the same call and feed the window uses, on batches 1..3.
Those three are what the plain reference follows.  The window then runs
batches 4, 5, ... for ``--seconds``, pipelined as ``launch/train.py``
does it: dispatch step t, prefetch batch t + 1, then block on step t-1's
loss.  It stops before the first step whose index is a multiple of the
subspace-update interval, so every window holds the same number of
tracking steps: none.
"""

from __future__ import annotations

import gc
import math
import time
from functools import partial

import numpy as np

from benchmarks.chip import flops as flops_lib
from benchmarks.chip import model as model_lib
from benchmarks.chip.bench import BENCH_DIR, BenchError
from benchmarks.chip.refs import subtrack

# The warm start's range finder is float32 arithmetic; on a TPU a jnp
# matmul of float32 operands runs one bfloat16 pass unless asked for more,
# and the program's own path does not ask (PERF.md, Open question 1).  The
# configuration states float32 optimizer arithmetic, so set-up runs the
# warm start at the precision that gives it.
WARM_START_PRECISION = "highest"


def program_config(conf, bench_dir=BENCH_DIR):
    """The program's ModelConfig for a configuration file: the file's
    depth, RoPE base and norm epsilon, and every size that the family's
    layout depends on checked against the program's."""
    from repro.configs.registry import get_config

    prog = conf["program"]
    cfg = get_config(prog["arch"], smoke=prog.get("smoke", False)).with_(
        n_layers=conf["num_hidden_layers"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"])
    pairs = {"d_model": (conf["hidden_size"], cfg.d_model),
             "vocab_size": (conf["vocab_size"], cfg.vocab_size),
             "tie_embeddings": (conf.get("tie_word_embeddings", False),
                                cfg.tie_embeddings),
             **model_lib.family(conf, bench_dir).program_keys(conf, cfg)}
    want = {k: w for k, (w, _) in pairs.items()}
    got = {k: g for k, (_, g) in pairs.items()}
    if got != want:
        raise BenchError(f"program config {prog['arch']} differs from the "
                         f"file: {got} != {want}")
    return cfg


def _slice_norms(flat: dict, stack_axes) -> dict:
    """{path: array} -> {path: float32 norms}, one per slice: the array
    indexed by its ``stack_axes(path)`` leading axes."""
    import jax.numpy as jnp

    out = {}
    for path, x in flat.items():
        x = x.astype(jnp.float32)
        axes = tuple(range(stack_axes(path), x.ndim))
        out[path] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


def _named(norms: dict) -> dict:
    """{path: norms} -> {slice name: float}, names as the reference's."""
    out = {}
    for path, v in norms.items():
        v = np.asarray(v)
        for idx in np.ndindex(v.shape):
            out[subtrack.slice_name(path, idx)] = float(v[idx])
    return out


def gap(prog: dict, refd: dict, keep=None) -> tuple[float, str]:
    """Worst slice of |prog - ref| / max(ref, median ref)."""
    names = [n for n in refd if keep is None or keep(n)]
    med = float(np.median([refd[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        g = abs(prog[n] - refd[n]) / max(refd[n], med, 1e-30)
        if g > worst:
            worst, where = g, n
    return worst, where


def lowrank_spread(prog: dict, refd: dict, names) -> tuple[float, float]:
    """Over the low-rank slices: the median of |prog - ref| / ref, and the
    size of the mean of (prog - ref) / ref, which the subspace's rotation
    moves either way from slice to slice and a lower precision one way."""
    rel = np.array([(prog[n] - refd[n]) / max(refd[n], 1e-30)
                    for n in names])
    return float(np.median(np.abs(rel))), float(abs(rel.mean()))


def compare(prog: dict, refr: dict) -> dict:
    """The numbers a training cell is held to, and those printed beside
    them.  The loss compared is the first step's: by the third step,
    early training at random weights swings so far that the float8
    control and the program read alike (PERF.md)."""
    med_raw = float(np.median(list(refr["first_raw"].values())))
    moved = lambda n: refr["first_raw"][n] >= 1e-3 * med_raw  # noqa: E731
    step_gaps = [abs(a - b) for a, b in zip(prog["losses"], refr["losses"])]
    loss_gap = step_gaps[0]
    dense_gap, dense_at = gap(prog["first_grad"], refr["first_grad"],
                              keep=lambda n: n in refr["dense"])
    grad_gap, grad_at = gap(prog["first_grad"], refr["first_grad"])
    change_gap, change_at = gap(prog["change"], refr["change"], keep=moved)
    left_out = sorted(n for n in refr["first_raw"] if not moved(n))
    low = [n for n in refr["first_grad"] if n not in refr["dense"]]
    lr_grad_med, lr_grad_bias = lowrank_spread(prog["first_grad"],
                                               refr["first_grad"], low)
    lr_change_med, lr_change_bias = lowrank_spread(
        prog["change"], refr["change"], [n for n in low if moved(n)])
    return {"loss_gap": loss_gap, "step_loss_gaps": step_gaps,
            "gnorm_gap": abs(prog["gnorm"] - refr["gnorm"]) / refr["gnorm"],
            "dense_grad_gap": dense_gap, "dense_grad_gap_at": dense_at,
            "grad_gap": grad_gap,
            "grad_gap_at": grad_at, "change_gap": change_gap,
            "change_gap_at": change_at, "left_out": left_out,
            "lowrank_grad_median": lr_grad_med,
            "lowrank_grad_bias": lr_grad_bias,
            "lowrank_change_median": lr_change_med,
            "lowrank_change_bias": lr_change_bias}


# compared where the workload gives a limit, printed otherwise
NUMBERS = ("loss_gap", "gnorm_gap", "dense_grad_gap", "grad_gap",
           "change_gap", "lowrank_grad_median", "lowrank_grad_bias",
           "lowrank_change_median", "lowrank_change_bias")


class Program:
    """The system under test, built for one training cell."""

    def __init__(self, conf, wl, bench_dir=BENCH_DIR):
        import jax

        from repro.core.api import get_optimizer
        from repro.core.lowrank_adam import AdamHP
        from repro.launch.steps import make_train_step, make_warm_start
        from repro.models.api import build_model

        self.conf, self.wl, self.bench_dir = conf, wl, bench_dir
        o = wl["optimizer"]
        self.cfg = program_config(conf, bench_dir)
        self.norms = jax.jit(partial(
            _slice_norms,
            stack_axes=model_lib.family(conf, bench_dir).stack_axes))
        self.bundle = build_model(self.cfg)
        model_lib.check_layout(
            jax.eval_shape(self.bundle.init, jax.random.PRNGKey(0)), conf,
            bench_dir)
        _check_init(o["init"])
        self.optimizer = get_optimizer(
            "subtrack", rank=o["rank"], update_interval=o["update_interval"],
            eta=o["eta"], init=o["init"]["method"], use_kernels=True,
            adam=AdamHP(beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
                        scale=o["scale"], zeta=o["zeta"]))
        step = make_train_step(self.bundle, self.optimizer,
                               clip_norm=o["clip_norm"], accum=wl["accum"],
                               remat=wl["remat"])
        self.step = jax.jit(step, static_argnames=("do_subspace_update",),
                            donate_argnums=(0,))
        self.warm = jax.jit(make_warm_start(self.bundle, self.optimizer,
                                            remat=wl["remat"]),
                            donate_argnums=(0,))
        self.init_opt = jax.jit(self.optimizer.init)

    def data(self, seed: int):
        from repro.data.pipeline import (DataConfig, SyntheticLMDataset,
                                         batch_for_model)

        import jax
        import jax.numpy as jnp

        ds = SyntheticLMDataset(DataConfig(
            vocab_size=self.conf["vocab_size"], seq_len=self.wl["seq_len"],
            global_batch=self.wl["batch"], seed=seed))
        # One compiled program for every batch: called eagerly, the
        # pipeline's lax.scan closure is traced and compiled anew for each
        # batch, which would put a compilation inside the window.
        fn = jax.jit(lambda i: batch_for_model(self.cfg, None, ds, i))
        return lambda i: fn(jnp.int32(i))

    def start(self, seed: int, batch, sample=lambda what: None,
              precision: str = WARM_START_PRECISION):
        """Weights, warm start and the three checked steps.  Returns the
        state (ready for step 4) and the program's readings.  ``sample``
        is called after the warm start and after the first step, to read
        the device's memory there."""
        import jax
        import jax.numpy as jnp

        from repro.launch.steps import TrainState

        params = model_lib.make_params(self.conf, seed, self.bench_dir)
        paths = [p for p, _ in model_lib.leaves(params)]
        state = TrainState(params=params, opt=self.init_opt(params))
        with jax.default_matmul_precision(precision):
            state, _ = self.warm(state, batch(0))
        jax.block_until_ready(state)
        sample("after the warm start")
        lr = jnp.float32(self.wl["optimizer"]["lr"])
        b1 = self.wl["optimizer"]["beta1"]
        losses, first_grad = [], None
        for i in (1, 2, 3):
            state, metrics = self.step(state, batch(i), lr)
            losses.append(metrics["loss"])
            if i == 1:
                jax.block_until_ready(state)
                sample("after step 1")
                gnorm = metrics["grad_norm"]
                moments = {p: _first_moment(state.opt.inner, p)
                           for p in paths}
                norms = jax.device_get(self.norms(moments))
                first_grad = {k: v / (1.0 - b1)
                              for k, v in _named(norms).items()}
        readings = {"losses": [float(x) for x in losses],
                    "gnorm": float(gnorm), "first_grad": first_grad,
                    "params": jax.device_get(state.params), "paths": paths}
        return state, lr, readings


def _check_init(init: dict) -> None:
    """The program's randomized warm start takes its seed, oversampling and
    power iterations from defaults; they must be the workload's."""
    import inspect

    from repro.core import subspace

    if init["method"] != "randomized":
        return
    sig = inspect.signature(subspace.init_subspace_randomized).parameters
    have = {"seed": sig["seed"].default,
            "oversample": sig["oversample"].default,
            "power_iters": sig["n_iter"].default}
    want = {k: init[k] for k in have}
    if have != want:
        raise BenchError(f"the program's randomized warm start is {have}, "
                         f"the workload states {want}")


def _first_moment(inner, path):
    node = inner
    for k in path:
        node = node[k]
    return node.M


def change_norms(conf, seed, host_params, paths,
                 bench_dir=BENCH_DIR) -> dict:
    """Per-slice ||P_3 - P_0|| of the program, P_0 drawn again from the
    seed (after the program's state is freed)."""
    import jax
    import jax.numpy as jnp

    p0 = model_lib.make_params(conf, seed, bench_dir)
    norms = jax.jit(partial(
        _slice_norms, stack_axes=model_lib.family(conf, bench_dir).stack_axes))
    out = {}
    diff = jax.jit(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32))
    for path in paths:
        a, b = host_params, p0
        for k in path:
            a, b = a[k], b[k]
        d = {path: diff(jnp.asarray(a), b)}
        out.update(_named(jax.device_get(norms(d))))
    del p0
    return out


def reference_readings(conf, wl, seed, batch, fmt=None, rows=None,
                       bench_dir=BENCH_DIR) -> dict:
    """The plain reference's readings: the family's float32 model (or its
    ``fmt`` control) trained by ``refs/subtrack.py`` from the seed's
    weights on batches 0..3."""
    fam = model_lib.family(conf, bench_dir)
    batches = [batch(i)["tokens"] for i in range(4)]
    return subtrack.train(fam.Model(conf, fmt), fam.stack_axes,
                          model_lib.make_params(conf, seed, bench_dir),
                          batches, dict(wl["optimizer"]), rows=rows)


def run(r) -> None:
    import jax

    from repro.kernels import ops

    conf, wl, bench_dir = r.cell.config, r.cell.workload, r.cell.bench_dir
    seed = model_lib.seed32(r.seed)
    prog = Program(conf, wl, bench_dir)
    batch = prog.data(seed)
    ops.reset_dispatch_tally()
    state, lr, readings = prog.start(seed, batch, sample=r.sample_memory)
    tally = ops.dispatch_tally()
    paths = {}
    for (entry, path, shapes), n in sorted(tally.items()):
        paths[path] = paths.get(path, 0) + 1
        r.log(f"dispatch {entry} {path} {list(shapes)} x{n}")
    r.log(f"dispatch tally by path: {paths}")
    k = wl["optimizer"]["update_interval"]
    r.log("tracking steps in the window: 0 (the window stops before "
          f"step {k})")
    r.setup_done()
    r.sample_memory("after set-up")

    B, S = wl["batch"], wl["seq_len"]
    idx = 4
    steps = 0
    with r.window() as w:
        nxt = batch(idx)
        inflight = None
        while time.perf_counter() - w.t0 < r.seconds:
            if idx % k == 0:
                raise BenchError(
                    f"the window reached step {idx}, a subspace update: "
                    "steps are now fast enough that run_seconds no longer "
                    "keeps tracking steps out of the window")
            with w.span("dispatch"):
                state, metrics = prog.step(state, nxt, lr)
            steps += 1
            with w.span("batch"):
                nxt = batch(idx + 1)
            if inflight is not None:
                with w.span("drain"):
                    loss = float(inflight["loss"])
                if not math.isfinite(loss):
                    r.failed += 1
            inflight = metrics
            idx += 1
        with w.span("drain"):
            loss = float(inflight["loss"])
        if not math.isfinite(loss):
            r.failed += 1
    r.attempted = steps
    seconds = w.t1 - w.t0
    tps = steps * B * S / seconds
    r.metric("train_tokens_per_s", tps, "tokens/s")
    r.read_memory()
    r.sample_memory("after the window")
    r.log(f"memory stats after the window: {r.devices[0].memory_stats()}")
    r.metric("peak_hbm_gib", r.hbm_footprint_bytes() / 2**30, "GiB")
    r.host.update(kind="train", steps=steps, window_s=seconds,
                  tokens_per_s=tps,
                  flops_per_token=flops_lib.train_flops_per_token(
                      conf, S, bench_dir),
                  lowrank=lowrank_shapes(conf, wl["optimizer"]["rank"],
                                         bench_dir))
    r.log(f"window: {steps} steps of {B}x{S} tokens in {seconds:.3f}s")

    del state, inflight, metrics, nxt
    gc.collect()
    readings["change"] = change_norms(conf, seed, readings.pop("params"),
                                      readings.pop("paths"), bench_dir)
    refr = reference_readings(conf, wl, seed, batch, bench_dir=bench_dir)
    c = compare(readings, refr)
    lim = wl["limits"]
    r.log("readings not compared: " + ", ".join(
        f"{k} {c[k]!r}" for k in NUMBERS if k not in lim))
    r.log(f"losses program {readings['losses']} reference "
          f"{refr['losses']}; gap per step {c['step_loss_gaps']}")
    r.log(f"worst first-gradient slice {c['grad_gap_at']}, worst change "
          f"slice {c['change_gap_at']}; change left out (reference "
          f"gradient under 1e-3 of the median slice): {c['left_out']}")
    for name, limit in lim.items():
        r.check(name, c[name], limit)


def lowrank_shapes(conf, rank,
                   bench_dir=BENCH_DIR) -> list[tuple[int, int, int]]:
    """(m, n, r) of every low-rank slice a plain step updates."""
    fam = model_lib.family(conf, bench_dir)
    out = []
    for path, shape in model_lib.leaves(fam.param_shapes(conf)):
        n = fam.stack_axes(path)
        if subtrack.is_lowrank(shape[n:], rank):
            out += [(min(shape[n:]), max(shape[n:]), rank)] * math.prod(
                shape[:n])
    return out
