"""SubTrack++ as a pytree-level gradient transform — plus every low-rank
baseline the paper compares against, sharing the same machinery.

The optimizer follows an optax-like protocol but with two extra entry
points demanded by the paper's algorithm and by production training:

* ``warm_start(state, grads)`` — installs S_0 from the first gradient
  (Alg. 1 line 1).  Kept out of the hot train step so the (one-time) SVD
  never bloats the compiled steady-state program.
* ``update(grads, state, params, lr, do_subspace_update)`` — the
  ``do_subspace_update`` flag is **static**: the training loop compiles two
  variants of the train step (plain / tracking) and picks per step on the
  host, mirroring how GaLore's reference implementation branches in Python.
  This keeps each compiled program single-purpose and makes the roofline
  of the k-1-of-k hot path cleanly measurable.

Subspace refresh methods (config ``method``):
    "grassmann"  — SubTrack++ geodesic tracking (the paper's contribution)
    "svd"        — GaLore / Fira periodic SVD re-initialization
    "random"     — GoLore-style random orthonormal refresh
    "osd"        — Online-Subspace-Descent-style Oja update + QR
    "grass"      — Grass-style structured-sparse basis (arXiv:2406.17660):
                   S selects the top-r gradient rows by row energy, so
                   every projection S^T G is an (r, n) gather — the
                   "grass" StepProgram regime with its local
                   ``sel_gather`` round
    "none"       — freeze the warm-started subspace (ablation; also the
                   setting of convergence Theorem 3.2)

Flag matrix reproducing the paper's method zoo:
    SubTrack++           method=grassmann, projection_aware=True,  recovery=True
    Grassmannian-only    method=grassmann, projection_aware=False, recovery=False
    GaLore               method=svd,       projection_aware=False, recovery=False
    Fira                 method=svd,       projection_aware=False, recovery=True
    GoLore               method=random,    projection_aware=False, recovery=False
    OSD                  method=osd,       projection_aware=False, recovery=False
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import health as health_lib
from repro.core import plan as plan_lib
from repro.core import program as program_lib
from repro.core import subspace as sub
from repro.core.lowrank_adam import (
    AdamHP,
    DenseOptState,
    MatrixOptState,
    dense_adam_step,
    init_dense_state,
    init_matrix_state,
    lowrank_adam_step,
    rotate_moments_dense,
    rotate_moments_rank1,
)

Array = jax.Array


@dataclass(frozen=True)
class LowRankConfig:
    """Everything that defines a low-rank optimizer variant (static)."""

    rank: int = 128
    update_interval: int = 200          # paper Table 10 (k)
    eta: float = 10.0                   # SubTrack++ step size (Table 10)
    method: str = "grassmann"
    projection_aware: bool = True
    recovery: bool = True
    init: str = "svd"                   # subspace warm-start (Eq. 1)
    # --- performance knobs (beyond-paper; defaults are paper-faithful) ---
    rank1_rotation: bool = False        # O(rn) PA rotation via geodesic structure
    fused_tangent: bool = True          # -2GA^T + 2S(AA^T) schedule (no residual)
    power_iters: int = 24
    exact_top1: bool = False            # eigh instead of power iteration
    reorth_interval: int = 0            # QR scrub every N subspace updates (0=off)
    use_kernels: bool = False           # Pallas kernels (fused single-pass hot path)
    # Row-regime Adam-state flavour: "replicated" recomputes the full-width
    # (r, n) M/V pass redundantly per row shard (zero extra collectives),
    # "reduce-scatter" shards M/V into n/g column slices (the plain step's
    # projection psum becomes a reduce-scatter + one epilogue all-gather —
    # per-device state memory AND the Adam pass shrink by the group
    # factor).  "auto" picks per leaf by the modeled per-device bytes
    # (repro.core.program._row_flavor; rs needs n divisible by the group).
    row_state: str = "auto"
    # Stack same-(m, n, rank) leaves into one vmapped launch per step instead
    # of one dispatch per leaf.  None (default) = auto: enabled on
    # single-device runs, and on sharded meshes whenever the optimizer was
    # built with (mesh, param_specs) — the spec-aware bucket_key then only
    # stacks identically-laid-out leaves, which is layout-preserving per
    # shard.  Spec-less multi-device runs still opt in explicitly with
    # True: without specs the flatten + concatenate can force GSPMD to
    # reshard differently-laid-out leaves into a common layout every step
    # (cf. the refuted lax.map experiment in plan.py — a measured 10x
    # memory blow-up on sharded expert banks).
    bucket_leaves: Optional[bool] = None
    osd_lr: float = 1e-2                # Oja step size for method="osd"
    adam: AdamHP = field(default_factory=AdamHP)
    weight_decay: float = 0.0


class OptState(NamedTuple):
    step: Array          # () int32 — number of updates applied
    n_updates: Array     # () int32 — number of subspace refreshes done
    inner: Any           # pytree over params of MatrixOptState / DenseOptState


class GradientTransform(NamedTuple):
    """The optimizer object handed to training loops."""

    init: Callable[[Any], OptState]
    warm_start: Callable[[OptState, Any], OptState]
    update: Callable[..., tuple[Any, OptState]]
    state_bytes: Callable[[Any], int]
    config: Any


def _get_backend(cfg: LowRankConfig):
    if not cfg.use_kernels:
        return None
    from repro.kernels import ops as kernel_ops  # lazy: kernels are optional

    return kernel_ops


# ---------------------------------------------------------------------------
# Per-matrix step functions (to be vmapped over stack dims)
# ---------------------------------------------------------------------------


def _plain_matrix_step(cfg: LowRankConfig, hp: AdamHP, G: Array,
                       st: MatrixOptState, step: Array, lr: Array,
                       param: Optional[Array], out_dtype, exec=None,
                       tap=None, with_health: bool = False):
    """``tap``, when given, is the grad-fused (r+1, n) [A; colnorms]
    panel emitted by the backward pass (models.common.tapped_matmul):
    rows [0:r] are the projection S^T G, row r the per-column ||G||^2 —
    handed down as the precomputed pair so the step never re-reads the
    full-width gradient for them."""
    pp = pg = None
    if tap is not None:
        pp, pg = tap[:-1], tap[-1]
    out = lowrank_adam_step(G, st, step, hp, recovery=cfg.recovery,
                            backend=_get_backend(cfg), lr=lr,
                            weight_decay=cfg.weight_decay, param=param,
                            out_dtype=out_dtype, exec=exec,
                            precomputed_proj=pp, precomputed_gsq=pg)
    if with_health:
        # plain steps run no geodesic — the all-healthy diag keeps the
        # output structure uniform for callers that request the report
        # on every step
        return out.delta, out.state, health_lib.zero_diag()
    return out.delta, out.state


def _refresh_subspace(cfg: LowRankConfig, G: Array, st: MatrixOptState,
                      step: Array, n_updates: Array, backend=None,
                      exec=None, eta_scale: float = 1.0):
    """Compute the new basis per the configured method.

    Returns (S_new, rank1_info, gsq, proj, diag): rank1_info is
    (cos_theta, v) for the Grassmann method (enabling the O(rn)
    rotation) and None otherwise; gsq is the per-column ||G_:,j||^2
    harvested by the fused Grassmann backend pass (basis-independent,
    reused by the Eq. 12 clip); proj is the globally-assembled NEW-basis
    projection when the program's gram schedule produced it (row-family
    regimes) — the epilogue then re-projects nothing; diag is the
    tracker's (health.DIAG_SIZE,) health vector (None for methods with
    no geodesic to guard).

    ``exec`` carries the leaf's StepProgram.  Only the Grassmann tracker
    (whose collectives are the program's declared rounds — see
    ``subspace.track_subspace``) and the frozen subspace are shardable;
    the SVD/random/Oja refreshes contract over all columns, so
    ``program.build_program`` never routes them here sharded.

    ``eta_scale`` is a static multiplier on the geodesic step size —
    1.0 everywhere except the sigma-blowup fault injection, which uses
    it to wrap theta past the clamp on one tracking step.
    """
    rank = st.S.shape[-1]
    if cfg.method == "grassmann":
        res = sub.track_subspace(
            st.S, G, eta=cfg.eta * eta_scale,
            fused_tangent=cfg.fused_tangent,
            exact_top1=cfg.exact_top1, power_iters=cfg.power_iters,
            backend=backend, exec=exec)
        S_new = res.S_new
        if cfg.reorth_interval:
            do = (n_updates % cfg.reorth_interval) == (cfg.reorth_interval - 1)
            S_new = jax.lax.cond(do, sub.reorthonormalize, lambda s: s, S_new)
            # after a QR scrub the rank-1 rotation identity no longer holds
            return S_new, None, res.gsq, res.A_new, res.diag
        return S_new, (res.cos_theta, res.v), res.gsq, res.A_new, res.diag
    if cfg.method == "svd":
        return sub.refresh_svd(G, rank), None, None, None, None
    if cfg.method == "random":
        return sub.refresh_random(G, rank, step=step), None, None, None, None
    if cfg.method == "grass":
        # Grass (arXiv:2406.17660): S <- the top-r coordinate rows by
        # gradient row energy — a structured-sparse one-hot selection
        # (trivially orthonormal), so every subsequent projection is the
        # program's ``sel_gather`` round instead of an MXU pass.
        G32 = G.astype(jnp.float32)
        _, idx = jax.lax.top_k(jnp.sum(G32 * G32, axis=1), rank)
        return jax.nn.one_hot(idx, G.shape[0], dtype=jnp.float32).T, \
            None, None, None, None
    if cfg.method == "osd":
        # Oja-style online PCA: S <- orth(S + lr * (I - SS^T) G G^T S)
        G32 = G.astype(jnp.float32)
        GS = G32.T @ st.S                        # (n, r)
        GGS = G32 @ GS                           # (m, r)
        corr = GGS - st.S @ (st.S.T @ GGS)
        return sub.reorthonormalize(st.S + cfg.osd_lr * corr), None, None, \
            None, None
    if cfg.method == "none":
        # frozen subspace: the change of basis is exactly I, expressed as
        # the rank-1 identity (cos_theta = 1, v = 0) so the rotation path
        # stays shard-local under row-family programs (the dense
        # Q = S^T S fallback would contract over sharded rows)
        return st.S, (jnp.float32(1.0), jnp.zeros(rank, jnp.float32)), \
            None, None, None
    raise ValueError(f"unknown subspace method {cfg.method!r}")


def _tracking_matrix_step(cfg: LowRankConfig, hp: AdamHP, G: Array,
                          st: MatrixOptState, step: Array, n_updates: Array,
                          lr: Array, param: Optional[Array], out_dtype,
                          exec=None, eta_scale: float = 1.0,
                          with_health: bool = False):
    """The 1-of-k subspace-update step, fused end to end when kernels are
    on: the program-scheduled subspace refresh (one read of G on the
    tangent schedule; the gram schedule's project/tangent/tangent_gram
    pipeline) -> geodesic -> O(rn) rank-1 rotation of (M, V) -> the same
    project/adam/fused_update epilogue the plain steps use (the column
    norms from the first launch feed the Eq. 12 clip, so no norm pass
    repeats; gram-schedule programs also hand the epilogue the
    already-assembled new-basis projection).  Without kernels this is the
    paper-literal unfused schedule.

    Every collective is a round of the leaf's StepProgram, executed by
    ``exec`` — see :mod:`repro.core.program` for the per-regime round
    tables."""
    backend = _get_backend(cfg)
    # the kernels (and their ref fallbacks) cast per tile, so keep the
    # gradient in its storage dtype on the fused path instead of
    # materializing an (m, n) fp32 copy up front
    Gc = G if backend is not None else G.astype(jnp.float32)

    S_new, rank1_info, gsq, proj, diag = _refresh_subspace(
        cfg, Gc, st, step, n_updates, backend, exec, eta_scale)

    rotated = None
    if cfg.projection_aware:
        # the rank-1 rotation is an exact rewrite of the dense one (the
        # geodesic's Q = I + (cos-1) vv^T), so the fused path always takes
        # it when available; cfg.rank1_rotation opts the jnp path in.
        # Under a sharded program cos_theta/v are replicated, so the
        # rotation runs per shard on whatever M/V block the state layout
        # holds (full width, column shard, or n/g slice) — it is
        # column-wise, so every layout is closed under it.
        if rank1_info is not None and (cfg.rank1_rotation
                                       or backend is not None):
            cos_t, v = rank1_info
            rotated = rotate_moments_rank1(cos_t, v, st.M, st.V, step, hp)
        else:
            Q = sub.change_of_basis(S_new, st.S)
            rotated = rotate_moments_dense(Q, st.M, st.V, step, hp)

    out = lowrank_adam_step(Gc, st, step, hp, rotated=rotated, S_new=S_new,
                            recovery=cfg.recovery, backend=backend,
                            lr=lr, weight_decay=cfg.weight_decay, param=param,
                            out_dtype=out_dtype, precomputed_proj=proj,
                            precomputed_gsq=gsq, exec=exec)
    if with_health:
        return out.delta, out.state, (diag if diag is not None
                                      else health_lib.zero_diag())
    return out.delta, out.state


def _warm_matrix_state(cfg: LowRankConfig, G: Array, st: MatrixOptState):
    G32 = G.astype(jnp.float32)
    rank = st.S.shape[-1]
    if cfg.method == "grass":
        # a one-hot selection basis from step 0: the grass program's
        # gather assumes S is ALWAYS a row selection (argmax recovers
        # the selected indices), so the dense SVD warm start would break
        # the invariant
        _, idx = jax.lax.top_k(jnp.sum(G32 * G32, axis=1), rank)
        return st._replace(
            S=jax.nn.one_hot(idx, G32.shape[0], dtype=jnp.float32).T)
    return st._replace(S=sub.init_subspace(G32, rank, cfg.init))


# ---------------------------------------------------------------------------
# The pytree-level transform
# ---------------------------------------------------------------------------


def _leaf_init(plan: plan_lib.ParamPlan, p: Array):
    if plan.mode == "dense":
        return init_dense_state(jnp.shape(p))
    shape = jnp.shape(p)
    stack = shape[:-2]
    st = init_matrix_state(plan.m, plan.n, plan.rank)
    if not stack:
        return st
    return MatrixOptState(
        S=jnp.broadcast_to(st.S, stack + st.S.shape),
        M=jnp.broadcast_to(st.M, stack + st.M.shape),
        V=jnp.broadcast_to(st.V, stack + st.V.shape),
        lam_prev=jnp.zeros(stack, jnp.float32),
    )


def lowrank_optimizer(cfg: LowRankConfig, *, mesh=None,
                      param_specs=None) -> GradientTransform:
    """Build the SubTrack++/GaLore/Fira/... optimizer for arbitrary pytrees.

    ``mesh`` + ``param_specs`` (a pytree of PartitionSpec mirroring the
    params) opt the fused hot path into mesh-native execution.  Per leaf
    (bucket), :func:`repro.core.program.build_program` classifies the
    canonical (m, n) sharding into a **StepProgram** — the declarative
    description of the regime, the Adam-state layout and every collective
    round the step may execute — and ONE lowering path
    (:func:`repro.core.program.lower`) turns it into the shard_map'd (or
    plain) step.  The regimes (full table in ``repro.core.program``):

    * **column** — canonical n sharded: shard-local except one scalar
      clip psum (plain) plus one (m, r) tangent psum (tracking);
    * **row** — canonical m sharded, replicated M/V: ONE stacked
      (r+1, n) [A; colnorms] psum per plain step (the clip closed form
      is then free), plus one fused (r, n + 3r) tangent-Gram psum on
      tracking steps (the tangent itself is row-local given global A);
    * **row-rs** — canonical m sharded, M/V reduce-scattered into n/g
      column slices (``cfg.row_state``): the projection psum becomes a
      reduce-scatter, the Adam pass runs sharded, and one epilogue
      all-gather restores full width before ``fused_update`` — 2
      collectives plain / 3 tracking, per-device state memory down by
      the group factor.

    Leaves outside every regime, and all runs built without mesh/specs,
    execute under plain GSPMD propagation (the replicated program).
    """

    hp = cfg.adam

    def init(params) -> OptState:
        plans = plan_lib.make_plans(params, cfg.rank)
        inner = jax.tree.map(_leaf_init, plans, params,
                             is_leaf=lambda x: isinstance(x, plan_lib.ParamPlan))
        return OptState(step=jnp.zeros((), jnp.int32),
                        n_updates=jnp.zeros((), jnp.int32), inner=inner)

    def warm_start(state: OptState, grads) -> OptState:
        plans = plan_lib.make_plans(grads, cfg.rank)

        def leaf(plan, g, st):
            if plan.mode == "dense":
                return st
            g = plan_lib.canonical_grad(g, plan)
            fn = functools.partial(_warm_matrix_state, cfg)
            fn = plan_lib.vmap_rank(fn, plan.batch_dims)
            return fn(g, st)

        inner = jax.tree.map(
            leaf, plans, grads, state.inner,
            is_leaf=lambda x: isinstance(x, plan_lib.ParamPlan))
        return state._replace(inner=inner)

    def update(grads, state: OptState, params, lr,
               do_subspace_update: bool = False, taps=None,
               with_health: bool = False, eta_scale: float = 1.0):
        """Returns (updates, new_state); updates are added to params.
        With ``with_health=True`` returns (updates, new_state, diag):
        ``diag`` is the max-aggregated (health.DIAG_SIZE,) subspace
        diagnostic over every low-rank leaf (raw sigma, applied theta,
        clamp/degenerate flags; all zeros on plain steps).  ``eta_scale``
        statically scales the Grassmann geodesic step size (fault
        injection only — the default compiles the identical program).

        Low-rank leaves emit the *final-dtype* update directly from the
        matrix step (lr, hp.scale, recovery clip and weight decay folded
        in — no pytree-level (m, n) pass), and leaves with identical
        canonical (m, n, rank) and parameter dtype are stacked into one
        vmapped launch per step (``cfg.bucket_leaves``).

        ``taps`` (optional) is a pytree mirroring ``grads`` whose leaves
        are either None or the grad-fused (..., r+1, n) [A; colnorms]
        panel the backward pass emitted for that leaf (canonical
        orientation, stack dims matching the gradient's).  Tapped leaves
        run solo with the ``grad-fused`` program: the plain step consumes
        the precomputed projection + colnorms and only the recovery
        residual pass touches full-width G.  Leaves whose program cannot
        legally consume a tap (row regimes, tracking steps) silently
        fall back to the untapped path — the tap is dropped, never
        misused.
        """
        plans = plan_lib.make_plans(grads, cfg.rank, specs=param_specs)
        step = state.step
        n_upd = state.n_updates
        lr32 = jnp.asarray(lr, jnp.float32)
        sharded_hotpath = mesh is not None and param_specs is not None
        # Bucketing auto-on: single-device always; multi-device once the
        # caller supplied specs (the spec-aware bucket_key then guarantees
        # stacking is layout-preserving on every shard — the cross-leaf
        # reshard blow-up that used to force multi-device opt-in cannot
        # occur).  Spec-less multi-device runs still require explicit
        # bucket_leaves=True.
        bucket = (cfg.bucket_leaves if cfg.bucket_leaves is not None
                  else jax.device_count() == 1 or sharded_hotpath)

        def leaf_program(plan, tapped=False):
            """The leaf's StepProgram — every regime decision (column vs
            row vs row-rs vs replicated vs grass, shardable refresh
            methods, reorth routing, grad-fused tap consumption) lives in
            ``program.build_program``; this layer only lowers and runs
            what the program declares."""
            return program_lib.build_program(
                plan, cfg, mesh if sharded_hotpath else None,
                tracking=do_subspace_update, tapped=tapped)

        def matrix_fn(out_dtype, exec):
            """Per-(m, n)-matrix step closure; ``p`` is threaded only when
            weight decay needs it (it is DCE'd otherwise), ``tap`` only
            on grad-fused plain steps."""
            if do_subspace_update:
                def base(G, s, p=None, tap=None):
                    return _tracking_matrix_step(cfg, hp, G, s, step, n_upd,
                                                 lr32, p, out_dtype, exec,
                                                 eta_scale, with_health)
            else:
                def base(G, s, p=None, tap=None):
                    return _plain_matrix_step(cfg, hp, G, s, step, lr32, p,
                                              out_dtype, exec, tap,
                                              with_health)
            return base

        def run_stacked(g2, st, p2, batch_dims, out_dtype, prog, tap=None):
            """Run the matrix step over a (possibly stacked) canonical
            gradient; returns (delta_stacked, new_state_stacked, diag) —
            ``diag`` is the stack-reduced (health.DIAG_SIZE,) health
            vector under ``with_health``, None otherwise.

            ONE lowering path for every regime: the per-matrix step is
            built against the program's executor (collectives by round
            name), vmapped over the stack dims, and handed to
            ``program.lower`` — which returns it unchanged for
            replicated programs and shard_map's it with
            program-derived in/out specs otherwise.
            """
            total_elems = int(np.prod(g2.shape)) // prog.shards
            exec = program_lib.executor(prog)
            base = matrix_fn(out_dtype, exec)
            wd = bool(cfg.weight_decay)
            if wd and tap is not None:
                fn = plan_lib.map_rank(lambda G, s, p, t: base(G, s, p, t),
                                       batch_dims, total_elems)
                args = (g2, st, p2, tap)
            elif wd:
                fn = plan_lib.map_rank(lambda G, s, p: base(G, s, p),
                                       batch_dims, total_elems)
                args = (g2, st, p2)
            elif tap is not None:
                fn = plan_lib.map_rank(lambda G, s, t: base(G, s, None, t),
                                       batch_dims, total_elems)
                args = (g2, st, tap)
            else:
                fn = plan_lib.map_rank(lambda G, s: base(G, s),
                                       batch_dims, total_elems)
                args = (g2, st)
            runner = program_lib.lower(prog, fn, mesh=mesh,
                                       batch_dims=batch_dims,
                                       with_param=wd,
                                       with_tap=tap is not None,
                                       with_health=with_health,
                                       kernels=cfg.use_kernels)
            out = runner(*args)
            if with_health:
                delta, new_st, diag = out
                return delta, new_st, health_lib.reduce_diag(diag)
            delta, new_st = out
            return delta, new_st, None

        def leaf_single(plan, g, st, p, tap=None):
            """Unbucketed path: one launch for one leaf (original layout —
            no extra reshapes, so sharded stacks keep their layout).
            The tap is consumed only when the leaf's program declares the
            ``grad_tap`` round (safe fallback otherwise)."""
            prog = leaf_program(plan, tapped=tap is not None)
            if prog.round("grad_tap") is None:
                tap = None
            g2 = plan_lib.canonical_grad(g, plan)
            p2 = plan_lib.canonical_grad(p, plan) if cfg.weight_decay else None
            delta, new_st, diag = run_stacked(g2, st, p2, plan.batch_dims,
                                              p.dtype, prog, tap=tap)
            return plan_lib.uncanonical_update(delta, plan), new_st, diag

        is_plan = lambda x: isinstance(x, plan_lib.ParamPlan)  # noqa: E731
        treedef = jax.tree.structure(plans, is_leaf=is_plan)
        plan_leaves = treedef.flatten_up_to(plans)
        grad_leaves = treedef.flatten_up_to(grads)
        state_leaves = treedef.flatten_up_to(state.inner)
        param_leaves = treedef.flatten_up_to(params)
        tap_leaves = (treedef.flatten_up_to(taps) if taps is not None
                      else [None] * len(plan_leaves))

        updates_out: list = [None] * len(plan_leaves)
        states_out: list = [None] * len(plan_leaves)
        health = health_lib.zero_diag() if with_health else None

        def absorb(diag):
            nonlocal health
            if with_health and diag is not None:
                health = health_lib.merge_diag(health, diag)

        # group low-rank leaves into same-(m, n, rank, dtype) buckets
        buckets: dict[tuple, list[int]] = {}
        for i, plan in enumerate(plan_leaves):
            if plan.mode == "dense":
                delta, new_st = dense_adam_step(grad_leaves[i],
                                                state_leaves[i], step, hp)
                p = param_leaves[i]
                upd = (-lr32 * delta).astype(p.dtype)
                if cfg.weight_decay:
                    upd = upd - (lr32 * cfg.weight_decay
                                 * p.astype(jnp.float32)).astype(p.dtype)
                updates_out[i], states_out[i] = upd, new_st
            else:
                key = plan_lib.bucket_key(plan, param_leaves[i].dtype)
                if plan_lib.spec_lead_sharded(plan):
                    # concatenating along a sharded stack axis would
                    # communicate — such leaves always run solo
                    key = key + ("solo", i)
                elif tap_leaves[i] is not None and not do_subspace_update:
                    # grad-fused leaves run solo: their program differs
                    # from untapped same-shape siblings' (the grad_tap
                    # round), and stacking would force every member of
                    # the bucket onto one path
                    key = key + ("tap", i)
                buckets.setdefault(key, []).append(i)

        for key, idxs in buckets.items():
            if not bucket or len(idxs) == 1:
                for i in idxs:
                    tap = (tap_leaves[i]
                           if not do_subspace_update else None)
                    updates_out[i], states_out[i], diag = leaf_single(
                        plan_leaves[i], grad_leaves[i], state_leaves[i],
                        param_leaves[i], tap=tap)
                    absorb(diag)
                continue

            # stack every member's matrices along one leading axis
            sizes, g_parts, p_parts, st_parts = [], [], [], []
            for i in idxs:
                plan = plan_leaves[i]
                g2 = plan_lib.canonical_grad(grad_leaves[i], plan)
                sizes.append(plan_lib.matrix_count(plan, g2.shape))
                g_parts.append(plan_lib.flatten_stack(g2, plan.batch_dims))
                if cfg.weight_decay:
                    p2 = plan_lib.canonical_grad(param_leaves[i], plan)
                    p_parts.append(plan_lib.flatten_stack(p2,
                                                          plan.batch_dims))
                st_parts.append(jax.tree.map(
                    lambda a, bd=plan.batch_dims: plan_lib.flatten_stack(
                        a, bd), state_leaves[i]))

            g_all = jnp.concatenate(g_parts, axis=0)
            p_all = jnp.concatenate(p_parts, axis=0) if cfg.weight_decay \
                else None
            st_all = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                  *st_parts)
            delta_all, st_new_all, diag = run_stacked(
                g_all, st_all, p_all, 1, param_leaves[idxs[0]].dtype,
                leaf_program(plan_leaves[idxs[0]]))
            absorb(diag)

            # split back to leaves and restore each one's stack layout
            splits = list(np.cumsum(sizes)[:-1])
            delta_split = jnp.split(delta_all, splits, axis=0)
            st_flat, st_def = jax.tree.flatten(st_new_all)
            st_pieces = [jnp.split(f, splits, axis=0) for f in st_flat]
            st_split = [jax.tree.unflatten(st_def, [p[k] for p in st_pieces])
                        for k in range(len(idxs))]
            for k, i in enumerate(idxs):
                plan = plan_leaves[i]
                lead = grad_leaves[i].shape[:plan.batch_dims]
                delta = plan_lib.unflatten_stack(delta_split[k],
                                                 plan.batch_dims, lead)
                updates_out[i] = plan_lib.uncanonical_update(delta, plan)
                states_out[i] = jax.tree.map(
                    lambda a, bd=plan.batch_dims, ls=lead:
                        plan_lib.unflatten_stack(a, bd, ls), st_split[k])

        updates = jax.tree.unflatten(treedef, updates_out)
        new_inner = jax.tree.unflatten(treedef, states_out)
        new_state = OptState(
            step=step + 1,
            n_updates=n_upd + (1 if do_subspace_update else 0),
            inner=new_inner)
        if with_health:
            return updates, new_state, health
        return updates, new_state

    def state_bytes(params) -> int:
        plans = plan_lib.make_plans(params, cfg.rank)
        total = 0
        for plan, p in zip(jax.tree.leaves(
                plans, is_leaf=lambda x: isinstance(x, plan_lib.ParamPlan)),
                jax.tree.leaves(params)):
            total += plan_lib.state_bytes(plan, tuple(jnp.shape(p)))
        return total

    return GradientTransform(init=init, warm_start=warm_start, update=update,
                             state_bytes=state_bytes, config=cfg)


# ---------------------------------------------------------------------------
# Named constructors for the paper's method zoo
# ---------------------------------------------------------------------------


def _build(overrides: dict) -> GradientTransform:
    """Split distribution kwargs (mesh, param_specs) from LowRankConfig
    fields so every named constructor accepts them uniformly."""
    mesh = overrides.pop("mesh", None)
    param_specs = overrides.pop("param_specs", None)
    return lowrank_optimizer(LowRankConfig(**overrides), mesh=mesh,
                             param_specs=param_specs)


def subtrack(**overrides) -> GradientTransform:
    """SubTrack++ (full): Grassmann tracking + projection-aware + recovery."""
    return _build(overrides)


def subtrack_fast(**overrides) -> GradientTransform:
    """SubTrack++ with all beyond-paper perf toggles on (§Perf variant)."""
    overrides.setdefault("rank1_rotation", True)
    overrides.setdefault("fused_tangent", True)
    return _build(overrides)


def grassmann_only(**overrides) -> GradientTransform:
    """Ablation: pure Grassmannian tracking (Fig. 3 baseline curve)."""
    overrides.setdefault("projection_aware", False)
    overrides.setdefault("recovery", False)
    return _build(overrides)


def galore(**overrides) -> GradientTransform:
    overrides.setdefault("method", "svd")
    overrides.setdefault("projection_aware", False)
    overrides.setdefault("recovery", False)
    return _build(overrides)


def fira(**overrides) -> GradientTransform:
    overrides.setdefault("method", "svd")
    overrides.setdefault("projection_aware", False)
    overrides.setdefault("recovery", True)
    return _build(overrides)


def golore(**overrides) -> GradientTransform:
    overrides.setdefault("method", "random")
    overrides.setdefault("projection_aware", False)
    overrides.setdefault("recovery", False)
    overrides.setdefault("init", "randomized")
    return _build(overrides)


def osd(**overrides) -> GradientTransform:
    overrides.setdefault("method", "osd")
    overrides.setdefault("projection_aware", False)
    overrides.setdefault("recovery", False)
    return _build(overrides)


def apollo(**overrides) -> GradientTransform:
    """APOLLO-flavoured baseline (Zhu et al., 2025): random projections +
    channel-wise scaling recovery — i.e. GoLore's subspace policy with
    Fira/SubTrack++'s recovery term (the scaling mechanism APOLLO shares)."""
    overrides.setdefault("method", "random")
    overrides.setdefault("projection_aware", False)
    overrides.setdefault("recovery", True)
    overrides.setdefault("init", "randomized")
    return _build(overrides)
