"""Train/serve step factories shared by train.py, serve.py and dryrun.py.

The train step is a single pure function over (TrainState, batch, lr):
value_and_grad -> global-norm clip -> optimizer update -> apply.  The
``do_subspace_update`` flag is static (two compiled variants — see
repro.core.subtrack); gradient accumulation microbatches via lax.scan.

The low-rank optimizers emit updates already in the parameter dtype with
lr/weight-decay folded in (the fused hot path under ``use_kernels`` writes
them in a single pass over G — see repro.kernels.grassmann), so the apply
below is a plain add; the ``astype`` is a no-op guard for baseline
optimizers that still return fp32 updates.
"""

from __future__ import annotations

import functools
import inspect
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import health as health_lib
from repro.core import program as program_lib
from repro.core.lowrank_adam import MatrixOptState
from repro.core.subtrack import GradientTransform, OptState
from repro.models.api import ModelBundle


class TrainState(NamedTuple):
    params: Any
    opt: OptState


def checkpoint_descriptors(params, optimizer, mesh=None, param_specs=None):
    """Per-param-leaf StateDescriptor pytree for ``optimizer``'s state —
    the record :func:`repro.checkpoint.transpose.state_program_records`
    embeds on save and the target the elastic restore transposes onto.
    Works for every optimizer (rank-less baseline configs yield all-dense
    descriptors)."""
    return program_lib.state_leaf_descriptors(
        params, optimizer.config, mesh=mesh, param_specs=param_specs)


def train_state_shardings(like: TrainState, descs, mesh,
                          param_shardings=None):
    """Target placement tree for an elastic restore of a TrainState:
    params follow the hot-path layout (``param_shardings``; replicated
    when absent), each MatrixOptState follows its descriptor's declared
    state layout (``sharding.descriptor_state_specs``), everything else
    replicates.  None when there is no mesh to place onto."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import sharding as sh

    rep = NamedSharding(mesh, P())
    params_sh = (param_shardings if param_shardings is not None
                 else jax.tree.map(lambda _: rep, like.params))
    inner_sh = jax.tree.map(
        lambda d, node: sh.descriptor_state_shardings(d, node, mesh),
        descs, like.opt.inner,
        is_leaf=lambda x: isinstance(x, program_lib.StateDescriptor))
    return TrainState(
        params=params_sh,
        opt=OptState(step=rep, n_updates=rep, inner=inner_sh))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float, taps=None):
    """Global-norm clip.  ``taps`` (optional, a pytree mirroring ``grads``
    with None at untapped leaves) lets grad-fused leaves contribute their
    backward-pass per-column ||G||^2 row — ``sum(tap[-1]) == ||G||_F^2``
    exactly — instead of a fresh full-width tree reduction; untapped
    leaves fall back to the plain square-and-sum."""
    if taps is None:
        norm = global_norm(grads)
    else:
        gdef = jax.tree.structure(grads)
        sq = jnp.zeros((), jnp.float32)
        for g, t in zip(jax.tree.leaves(grads), gdef.flatten_up_to(taps)):
            if t is None:
                sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
            else:
                sq = sq + jnp.sum(t[..., -1, :])
        norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), norm


# ---------------------------------------------------------------------------
# Grad-fused tap collection
# ---------------------------------------------------------------------------
#
# The taggable matmul sites of the decoder family (repro.models.transformer):
# per-layer attention / MLP projections plus the untied lm_head.  MLA
# attention and MoE blocks have no taggable dense path — their sites are
# simply absent, and the model falls back to vanilla matmuls there.


def _tap_paths(cfg) -> list[tuple[str, ...]]:
    paths: list[tuple[str, ...]] = []
    if getattr(cfg, "attn_type", None) != "mla":
        paths += [("layers", "attn", k) for k in ("wq", "wk", "wv", "wo")]
    if getattr(cfg, "moe", None) is None:
        paths += [("layers", "mlp", k) for k in ("w_gate", "w_up", "w_down")]
    paths.append(("lm_head",))
    return paths


def _site_get(tree, path):
    for k in path:
        if not isinstance(tree, dict) or k not in tree:
            return None
        tree = tree[k]
    return tree


def _site_set(tree, path, val):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = val


def _none_like(tree):
    """Same nested-dict skeleton, every leaf None — the all-untapped taps
    pytree the optimizer's flatten_up_to pairs with the gradients."""
    if isinstance(tree, dict):
        return {k: _none_like(v) for k, v in tree.items()}
    return None


def guarded_apply(state: TrainState, updates, new_opt,
                  report: health_lib.HealthReport) -> TrainState:
    """Quarantine gate around the parameter/optimizer apply: when the
    step's :class:`~repro.core.health.HealthReport` fails (non-finite
    loss, global grad norm, or update norm), the WHOLE TrainState is
    kept bit-identical — params, Adam moments (M, V), the subspace S and
    the Adam step count — matching loss-scaling skip semantics.  Healthy
    steps apply exactly what the un-guarded step applied.

    The gate is a per-leaf select, not a ``lax.cond`` over the whole
    state: XLA fuses each select into the computation of its new value
    and writes it in place over the donated old buffer, where a cond must
    hold a complete second TrainState until the verdict (about 5 GiB more
    HBM for the 1B model at rank 512)."""
    ok = health_lib.step_ok(report)
    params = jax.tree.map(
        lambda p, u: jnp.where(ok, p + u.astype(p.dtype), p),
        state.params, updates)
    opt = jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                       new_opt, state.opt)
    return TrainState(params=params, opt=opt)


def make_train_step(bundle: ModelBundle, optimizer: GradientTransform,
                    *, clip_norm: float = 1.0, accum: int = 1,
                    remat: str = "full", grad_shardings=None,
                    accum_dtype=jnp.float32, grad_fused: bool = False,
                    inject: bool = False):
    """Returns train_step(state, batch, lr, *, do_subspace_update) ->
    (state, metrics).  Donate ``state`` when jitting.

    Every step emits a :class:`repro.core.health.HealthReport` in its
    metrics (assembled from reductions the step already produces — no
    extra pass over the gradients) and quarantines itself through
    :func:`guarded_apply` when the report fails.

    ``inject=True`` adds a traced int32 ``inject_code`` positional after
    ``lr`` plus a static ``eta_scale`` keyword (the in-graph half of the
    ``--inject`` fault surface; see ``repro.core.health`` for the codes).
    The default builds the exact pre-injection program.

    ``grad_shardings`` (pytree of NamedSharding matching params) pins each
    per-microbatch gradient to the parameter's layout *in the gradient's
    native bf16* — GSPMD then lowers the cross-data reduction as a bf16
    reduce-scatter (ZeRO-2) instead of a full fp32 all-reduce per
    microbatch: 4x less gradient wire traffic (§Perf iteration 1).
    The fp32 accumulator carries the same sharding, so accumulation and
    the (sharded-state) optimizer add no further collectives.

    ``grad_fused`` opts the k-1-of-k plain steps into the grad-fused
    backward: the taggable matmuls run through
    ``models.common.tapped_matmul``, whose custom vjp emits each leaf's
    (r+1, n) [A = S^T G; per-column ||G||^2] panel WHILE forming the
    weight cotangent, and the optimizer consumes the panel instead of
    re-projecting the full-width gradient (the tapped colnorms also
    serve the global-norm clip).  Safe fallbacks, all silent: gradient
    accumulation (per-microbatch taps are not additive — sum_i ||G_i||^2
    != ||sum_i G_i||^2), model families without ``loss_taps``, tracking
    steps, untaggable leaves (embeddings, MoE banks, MLA attention), and
    leaves whose StepProgram rejects the tap (row-sharded regimes) all
    take the vanilla path.
    """

    loss_fn = functools.partial(bundle.loss, remat=remat)
    use_taps = (grad_fused and accum == 1
                and bundle.loss_taps is not None)
    tap_paths = _tap_paths(bundle.cfg) if use_taps else []
    upd_params = inspect.signature(optimizer.update).parameters
    has_health = "with_health" in upd_params
    has_eta_scale = "eta_scale" in upd_params

    def _pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            grad_shardings)

    def grads_of(params, batch, gscale=None):
        """``gscale`` (traced scalar, injection only) scales the loss
        VALUE fed to the backward — the cotangent seeds with it, so
        every gradient leaf is scaled without an extra pass — while the
        TRUE loss reaches the metrics through the aux channel."""
        if gscale is None:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, _pin(grads)

        def inj_loss(p, b):
            loss, metrics = loss_fn(p, b)
            return loss * gscale, (loss, metrics)

        (_, (loss, metrics)), grads = jax.value_and_grad(
            inj_loss, has_aux=True)(params, batch)
        return loss, metrics, _pin(grads)

    def accum_grads(params, batch, gscale=None):
        if accum == 1:
            return grads_of(params, batch, gscale)
        # split the leading batch dim into `accum` microbatches and scan
        def resh(x):
            return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])

        micro = jax.tree.map(resh, batch)
        zeros = _pin(jax.tree.map(
            lambda p: jnp.zeros(p.shape, accum_dtype), params))

        def step(carry, mb):
            g_acc, l_acc = carry
            loss, metrics, g = grads_of(params, mb, gscale)
            g_acc = _pin(jax.tree.map(
                lambda a, b: a + b.astype(accum_dtype) / accum, g_acc, g))
            return (g_acc, l_acc + loss / accum), metrics

        (grads, loss), metrics = jax.lax.scan(step, (zeros, 0.0), micro)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss, metrics, grads

    def tapped_grads(state: TrainState, batch, gscale=None):
        """One backward over (params, seeds): the seeds' cotangents ARE
        the per-leaf [A; colnorms] tap panels (see tapped_matmul)."""
        sites = []
        for path in tap_paths:
            p = _site_get(state.params, path)
            st = _site_get(state.opt.inner, path)
            if p is None or not isinstance(st, MatrixOptState):
                continue  # absent leaf (tied lm_head) or dense plan
            sites.append((path, st.S, st.M.shape[-1]))
        if not sites:
            loss, metrics, grads = grads_of(state.params, batch, gscale)
            return loss, metrics, grads, None

        seeds: dict = {}
        for path, S, n in sites:
            r = S.shape[-1]
            _site_set(seeds, path,
                      jnp.zeros(S.shape[:-2] + (r + 1, n), jnp.float32))

        def loss_with_taps(params, sd):
            taps_in: dict = {}
            for path, S, n in sites:
                _site_set(taps_in, path, (S, _site_get(sd, path)))
            loss, metrics = bundle.loss_taps(params, batch, taps_in,
                                             remat=remat)
            if gscale is None:
                return loss, (loss, metrics)
            # the tap panels are cotangents too, so they scale with the
            # gradients — A by gscale, the squared colnorms by gscale
            # (they are linear in the seed): a NaN'd backward poisons
            # them consistently and the tap-fed clip norm catches it
            return loss * gscale, (loss, metrics)

        (_, (loss, metrics)), (grads, tap_grads) = jax.value_and_grad(
            loss_with_taps, argnums=(0, 1), has_aux=True)(
                state.params, seeds)
        taps = _none_like(state.params)
        for path, S, n in sites:
            _site_set(taps, path, _site_get(tap_grads, path))
        return loss, metrics, _pin(grads), taps

    def step_core(state: TrainState, batch, lr, inject_code,
                  do_subspace_update: bool, eta_scale: float):
        gscale = None
        if inject_code is not None:
            gscale = jnp.where(inject_code == health_lib.INJECT_NAN_GRAD,
                               jnp.float32(jnp.nan), jnp.float32(1.0))
        taps = None
        if use_taps and not do_subspace_update:
            loss, metrics, grads, taps = tapped_grads(state, batch, gscale)
        else:
            loss, metrics, grads = accum_grads(state.params, batch, gscale)
        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, clip_norm, taps=taps)
            if taps is not None:
                # the clip rescales G by s, so A scales by s and the
                # squared column norms by s^2
                s = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
                taps = jax.tree.map(
                    lambda t: jnp.concatenate(
                        [t[..., :-1, :] * s, t[..., -1:, :] * (s * s)],
                        axis=-2), taps)
        opt_kw = {} if taps is None else {"taps": taps}
        if has_eta_scale and eta_scale != 1.0:
            opt_kw["eta_scale"] = eta_scale
        with_health = has_health and do_subspace_update
        with jax.named_scope("optimizer"):
            if with_health:
                opt_kw["with_health"] = True
                updates, opt, diag = optimizer.update(
                    grads, state.opt, state.params, lr,
                    do_subspace_update=do_subspace_update, **opt_kw)
            else:
                diag = None
                updates, opt = optimizer.update(
                    grads, state.opt, state.params, lr,
                    do_subspace_update=do_subspace_update, **opt_kw)
        with jax.named_scope("apply"):
            if inject_code is not None:
                # loss-spike: amplify AND NEGATE the applied update (fused
                # into the apply, which reads every update leaf anyway) —
                # a huge ascent step raises the loss in any training
                # phase, where a huge descent step can accidentally help
                # early on.  The step itself stays finite/healthy, only
                # the FOLLOWING steps' losses spike, which is the host
                # sentinel's case to catch
                amp = jnp.where(inject_code == health_lib.INJECT_LOSS_SPIKE,
                                jnp.float32(-health_lib.LOSS_SPIKE_AMP),
                                jnp.float32(1.0))
                updates = jax.tree.map(
                    lambda u: (u.astype(jnp.float32) * amp).astype(u.dtype),
                    updates)
            # the apply reads every update leaf, so XLA fuses this
            # reduction into the same pass — the report costs no extra
            # gradient reads
            unorm = global_norm(updates)
            report = health_lib.make_report(loss, gnorm, unorm, diag)
            new_state = guarded_apply(state, updates, opt, report)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr,
                       **health_lib.report_metrics(report))
        return new_state, metrics

    if inject:
        def train_step(state: TrainState, batch, lr, inject_code,
                       *, do_subspace_update: bool = False,
                       eta_scale: float = 1.0):
            return step_core(state, batch, lr, inject_code,
                             do_subspace_update, eta_scale)
    else:
        def train_step(state: TrainState, batch, lr,
                       *, do_subspace_update: bool = False):
            return step_core(state, batch, lr, None,
                             do_subspace_update, 1.0)

    return train_step


def make_warm_start(bundle: ModelBundle, optimizer: GradientTransform,
                    remat: str = "full"):
    """warm_start(state, batch) -> (state, loss) — installs S_0 from the
    first gradient and surfaces the warm-start loss (value_and_grad; the
    old bare ``jax.grad`` discarded it, hiding divergent inits at
    step 0)."""
    loss_fn = functools.partial(bundle.loss, remat=remat)

    def warm(state: TrainState, batch):
        with jax.named_scope("warm_start"):
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch)
            return TrainState(params=state.params,
                              opt=optimizer.warm_start(state.opt, grads)), loss

    return warm


def make_serve_steps(bundle: ModelBundle, max_len: int):
    """(prefill_step, decode_step) pair for serving/dry-run."""

    def prefill_step(params, batch):
        return bundle.prefill(params, batch, max_len)

    def decode_step(params, cache, token):
        return bundle.decode_step(params, cache, token)

    return prefill_step, decode_step


def default_accum(global_batch: int, seq_len: int, dp: int,
                  tokens_per_micro: int = 8192) -> int:
    """Gradient-accumulation depth so each microbatch holds ~8k tokens per
    device — keeps scan-over-layers boundary activations (L x B_loc x S x d)
    inside HBM for the big train cells (DESIGN.md §5).

    Constraints: accum | global_batch and dp | (global_batch / accum) so the
    microbatch still shards evenly over the DP axes.  Selection: the
    smallest valid accum >= target (fewest scan iterations that still fit),
    else the largest valid one; 1 when no divisor satisfies the DP
    constraint (i.e. dp doesn't divide global_batch at all).

    Enumerates divisors directly in O(sqrt(global_batch)) — the previous
    linear scan walked every integer up to global_batch, which at
    production global batches (256k sequences and beyond) is millions of
    iterations on the launcher's critical path.
    """
    dp = max(dp, 1)
    target = max(1, (global_batch // dp) * seq_len // tokens_per_micro)
    divisors = set()
    d = 1
    while d * d <= global_batch:
        if global_batch % d == 0:
            divisors.add(d)
            divisors.add(global_batch // d)
        d += 1
    valid = [a for a in divisors if (global_batch // a) % dp == 0]
    if not valid:
        return 1
    at_least = [a for a in valid if a >= target]
    return min(at_least) if at_least else max(valid)


def default_rank(d_model: int) -> int:
    """Paper Table 10 rank ladder mapped onto the assigned archs'
    hidden sizes (1024-rank at 7B-scale widths, 512 at 1B-3B widths...)."""
    if d_model >= 6144:
        return 1024
    if d_model >= 2048:
        return 512
    if d_model >= 1024:
        return 256
    return 128
