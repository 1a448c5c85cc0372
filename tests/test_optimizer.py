"""Pytree-level optimizer tests: plans, state memory (paper Table 2),
convergence behaviour (Theorem 3.2 flavour), the method zoo, and the
Pallas-kernel-backed path."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as plan_lib
from repro.core.api import get_optimizer, optimizer_names
from repro.core.subtrack import LowRankConfig, lowrank_optimizer


def _toy():
    key = jax.random.PRNGKey(0)
    params = {"w": 0.5 * jax.random.normal(key, (24, 48)),
              "emb": 0.5 * jax.random.normal(jax.random.fold_in(key, 1),
                                             (64, 16)),
              "b": jnp.zeros((48,))}
    x = jax.random.normal(jax.random.fold_in(key, 2), (16, 24))

    def loss_fn(p, x):
        y = jnp.tanh(x @ p["w"] + p["b"])
        z = y[:, :16] @ p["emb"].T
        return jnp.mean(z ** 2) + jnp.mean(y ** 2)

    return params, x, loss_fn


def _run(opt, params, x, loss_fn, steps=50, lr=0.05, k=5):
    state = opt.init(params)
    state = opt.warm_start(state, jax.grad(loss_fn)(params, x))
    upd = jax.jit(opt.update, static_argnames=("do_subspace_update",))
    p = params
    for s in range(steps):
        g = jax.grad(loss_fn)(p, x)
        u, state = upd(g, state, p, lr,
                       do_subspace_update=(s > 0 and s % k == 0))
        p = jax.tree.map(lambda a, b: a + b, p, u)
    return float(loss_fn(p, x)), p, state


class TestPlans:
    def test_plan_modes(self):
        assert plan_lib.plan_for_shape((48,), 8).mode == "dense"
        assert plan_lib.plan_for_shape((4, 4), 8).mode == "dense"  # min<=rank
        p = plan_lib.plan_for_shape((64, 32), 8)
        assert p.mode == "lowrank" and p.transpose and p.m == 32 and p.n == 64
        p = plan_lib.plan_for_shape((3, 16, 64), 8)
        assert p.batch_dims == 1 and not p.transpose

    def test_state_bytes_matches_paper_formula(self):
        """Table 2: low-rank optimizer stores mr + 2nr fp32 per matrix
        (+1 limiter scalar) vs Adam's 2mn."""
        shape, r = (128, 256), 16
        p = plan_lib.plan_for_shape(shape, r)
        got = plan_lib.state_bytes(p, shape)
        m, n = 128, 256
        assert got == (m * r + 2 * n * r + 1) * 4

    def test_optimizer_memory_below_adam(self):
        params, x, loss_fn = _toy()
        lowrank = get_optimizer("subtrack", rank=4)
        adam = get_optimizer("adamw")
        assert lowrank.state_bytes(params) < 0.5 * adam.state_bytes(params)


class TestConvergence:
    def test_all_methods_reduce_loss(self):
        params, x, loss_fn = _toy()
        l0 = float(loss_fn(params, x))
        for name in optimizer_names():
            if name == "badam":
                continue  # needs many block cycles on this tiny problem
            kw = {} if name == "adamw" else {"rank": 4, "update_interval": 5}
            l1, _, _ = _run(get_optimizer(name, **kw), params, x, loss_fn)
            assert l1 < l0 * 0.9, f"{name}: {l0} -> {l1}"

    def test_projected_gradient_norm_decreases_fixed_subspace(self):
        """Theorem 3.2 setting: fixed subspace (method='none'), rho=1
        (bias_correction off, raw SGD-like) on a PSD quadratic — ||P_t||
        must contract monotonically (up to small numerical wiggle)."""
        from repro.core.lowrank_adam import AdamHP
        key = jax.random.PRNGKey(3)
        A = jax.random.normal(key, (24, 24)) / 5.0
        Q = A @ A.T + 0.5 * jnp.eye(24)   # PSD, bounded spectrum

        def loss_fn(p, _):
            return 0.5 * jnp.trace(p["w"].T @ Q @ p["w"]).astype(jnp.float32)

        params = {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                         (24, 48))}
        opt = lowrank_optimizer(LowRankConfig(
            rank=6, method="none", projection_aware=False, recovery=False,
            adam=AdamHP(beta1=0.0, beta2=0.0, eps=1e9, scale=1.0,
                        bias_correction=False)))
        # eps >> grads makes Adam's denominator ~constant => plain projected GD
        state = opt.init(params)
        g0 = jax.grad(loss_fn)(params, None)
        state = opt.warm_start(state, g0)
        S = state.inner["w"].S
        p = params
        norms = []
        for s in range(25):
            g = jax.grad(loss_fn)(p, None)
            norms.append(float(jnp.linalg.norm(S.T @ g["w"])))
            u, state = opt.update(g, state, p, 3e7)
            p = jax.tree.map(lambda a, b: a + b, p, u)
        assert norms[-1] < 0.5 * norms[0]
        # mostly-monotone decrease
        increases = sum(b > a * 1.01 for a, b in zip(norms, norms[1:]))
        assert increases <= 2

    def test_subtrack_fast_matches_subtrack_closely(self):
        """rank-1 rotation + fused tangent are exact rewrites: trajectories
        must track each other to numerical tolerance."""
        params, x, loss_fn = _toy()
        l_a, p_a, _ = _run(get_optimizer("subtrack", rank=4,
                                         update_interval=5),
                           params, x, loss_fn, steps=30)
        l_b, p_b, _ = _run(get_optimizer("subtrack_fast", rank=4,
                                         update_interval=5),
                           params, x, loss_fn, steps=30)
        assert abs(l_a - l_b) < 0.05 * abs(l_a) + 1e-3

    def test_badam_updates_only_active_block(self):
        params, x, loss_fn = _toy()
        opt = get_optimizer("badam", block_interval=100, n_blocks=3)
        state = opt.init(params)
        g = jax.grad(loss_fn)(params, x)
        u, _ = opt.update(g, state, params, 0.1)
        flat = jax.tree.leaves(u)
        active = [bool(jnp.any(jnp.abs(x) > 0)) for x in flat]
        assert sum(active) == 1  # only block 0 of 3 moves at step 0


class TestWarmStart:
    def test_warm_start_installs_orthonormal_bases(self):
        params, x, loss_fn = _toy()
        opt = get_optimizer("subtrack", rank=4)
        state = opt.init(params)
        state = opt.warm_start(state, jax.grad(loss_fn)(params, x))
        S = state.inner["w"].S
        np.testing.assert_allclose(S.T @ S, np.eye(4), atol=1e-5)

    def test_stacked_params_get_per_slice_subspaces(self):
        key = jax.random.PRNGKey(1)
        params = {"layers": jax.random.normal(key, (3, 16, 32))}
        grads = {"layers": jax.random.normal(jax.random.fold_in(key, 1),
                                             (3, 16, 32))}
        opt = get_optimizer("subtrack", rank=4)
        state = opt.warm_start(opt.init(params), grads)
        S = state.inner["layers"].S          # (3, 16, 4)
        assert S.shape == (3, 16, 4)
        for i in range(3):
            np.testing.assert_allclose(S[i].T @ S[i], np.eye(4), atol=1e-5)
        # slices differ (independent subspaces)
        assert float(jnp.abs(S[0] - S[1]).max()) > 1e-3


def _driven_updates(opt, params, grad_at, steps, lr=0.03, k=4):
    """Run ``opt`` against an externally supplied gradient schedule and
    collect the per-step updates.  Unlike a closed training loop, this
    keeps the comparison well-conditioned: Adam's elementwise
    normalization makes closed-loop trajectories chaotically sensitive to
    fp-level arithmetic differences (any near-zero gradient entry turns a
    1e-8 perturbation into an O(1) direction change), which would test
    the problem's conditioning rather than the schedules' equivalence."""
    state = opt.init(params)
    state = opt.warm_start(state, grad_at(0))
    upd = jax.jit(opt.update, static_argnames=("do_subspace_update",))
    updates = []
    for s in range(steps):
        u, state = upd(grad_at(s), state, params, lr,
                       do_subspace_update=(s > 0 and s % k == 0))
        updates.append(u)
    return updates, state


class TestKernelBackend:
    def test_kernel_path_matches_reference_path(self, monkeypatch):
        """Fused single-pass kernel schedule vs the unfused jnp reference,
        per-step over a multi-step run with recovery + Eq. 12 clipping
        active (growing gradient scale keeps the limiter engaged).

        eta is chosen so the geodesic angle theta = eta * sigma stays O(1):
        at the paper's eta = 10 with sigma ~ 1e3-1e4, theta wraps the circle
        thousands of times and cos/sin(theta) amplify a 1e-7 fp difference
        in sigma (fused vs unfused tangent schedules associate differently)
        into an O(1) basis change — that would test angle-wrap chaos, not
        schedule equivalence."""
        monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
        # 24x48 doesn't tile 256 blocks — use a tile-friendly param set
        key = jax.random.PRNGKey(9)
        params = {"w": 0.1 * jax.random.normal(key, (256, 512))}

        def grad_at(s):
            return {"w": (1.0 + 0.3 * s) * jax.random.normal(
                jax.random.fold_in(key, 100 + s), (256, 512))}

        opt_ref = get_optimizer("subtrack", rank=64, update_interval=4,
                                eta=2e-5)
        opt_ker = get_optimizer("subtrack", rank=64, update_interval=4,
                                eta=2e-5, use_kernels=True)
        state = opt_ref.init(params)
        state = opt_ref.warm_start(state, grad_at(0))
        upd_ref = jax.jit(opt_ref.update,
                          static_argnames=("do_subspace_update",))
        upd_ker = jax.jit(opt_ker.update,
                          static_argnames=("do_subspace_update",))
        clipped = False
        for s in range(20):
            g = grad_at(s)
            do = s > 0 and s % 4 == 0
            # both schedules from the identical state: per-step equivalence
            # along a real 20-step state trajectory (comparing freely
            # co-evolving runs instead would measure fp32 ulp drift
            # amplified by Adam's normalization, not the schedules)
            u_ref, state_next = upd_ref(g, state, params, 0.03,
                                        do_subspace_update=do)
            u_ker, state_ker = upd_ker(g, state, params, 0.03,
                                       do_subspace_update=do)
            rel = float(jnp.max(jnp.abs(u_ref["w"] - u_ker["w"]))
                        / (jnp.max(jnp.abs(u_ref["w"])) + 1e-12))
            # tracking steps run entirely different (mathematically
            # equivalent) schedules — fused tangent kernel + rank-1
            # rotation vs jnp tangent + dense rotation — and Adam's
            # m/(sqrt(v)+eps) normalization amplifies fp-level differences
            # in the rotated second moment wherever v is small, so they
            # carry a larger fp budget than the plain steps
            assert rel < (1e-3 if do else 1e-5), (s, rel)
            np.testing.assert_allclose(state_next.inner["w"].lam_prev,
                                       state_ker.inner["w"].lam_prev,
                                       rtol=1e-4)
            lam = float(state.inner["w"].lam_prev)
            clipped |= lam > 0 and float(
                state_next.inner["w"].lam_prev) >= 0.99 * 1.01 * lam
            state = state_next
        # the Eq. 12 limiter actually engaged during the run
        assert float(state.inner["w"].lam_prev) > 0
        assert clipped

    def test_tracking_closed_loop_fused_matches_unfused(self, monkeypatch):
        """Closed loop with the subspace update firing repeatedly: both
        paths free-run their own state (S, M, V, lam) and parameters;
        after four tracking steps the trajectories must still agree on
        every piece of state within fp tolerance.

        The fused path exercises the full tracking pipeline:
        project_tangent_colnorms (one read of G for A + column norms +
        tangent) -> geodesic -> rank-1 (M, V) rotation -> fused epilogue
        reusing the harvested norms for the Eq. 12 clip.  Gradient scale
        is kept gentle so the geodesic angle theta = eta * sigma stays
        well-conditioned (see test_kernel_path_matches_reference_path)."""
        monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
        key = jax.random.PRNGKey(11)
        params = {"w": 0.1 * jax.random.normal(key, (256, 512))}

        def grad_at(s):
            return {"w": (1.0 + 0.05 * s) * jax.random.normal(
                jax.random.fold_in(key, 200 + s), (256, 512))}

        kw = dict(rank=64, update_interval=3, eta=2e-5)
        opt_ref = get_optimizer("subtrack", **kw)
        opt_ker = get_optimizer("subtrack", use_kernels=True, **kw)

        def run(opt):
            state = opt.init(params)
            state = opt.warm_start(state, grad_at(0))
            upd = jax.jit(opt.update, static_argnames=("do_subspace_update",))
            p = params
            for s in range(13):                 # tracking at s=3,6,9,12
                u, state = upd(grad_at(s), state, p, 0.03,
                               do_subspace_update=(s > 0 and s % 3 == 0))
                p = jax.tree.map(lambda a, b: a + b, p, u)
            return p, state

        p_ref, st_ref = run(opt_ref)
        p_ker, st_ker = run(opt_ker)
        assert int(st_ref.n_updates) == 4

        def rel(a, b):
            return float(jnp.max(jnp.abs(a - b))
                         / (jnp.max(jnp.abs(a)) + 1e-12))

        # the basis itself: the geodesic steps agreed throughout
        assert rel(st_ref.inner["w"].S, st_ker.inner["w"].S) < 1e-4
        # rotated Adam moments (rank-1 vs dense rotation are exact
        # rewrites; differences are accumulated fp noise)
        assert rel(st_ref.inner["w"].M, st_ker.inner["w"].M) < 1e-3
        assert rel(st_ref.inner["w"].V, st_ker.inner["w"].V) < 1e-3
        np.testing.assert_allclose(st_ref.inner["w"].lam_prev,
                                   st_ker.inner["w"].lam_prev, rtol=1e-3)
        # parameters after the full closed loop
        assert rel(p_ref["w"], p_ker["w"]) < 1e-3

    def test_fused_updates_are_final_dtype(self, monkeypatch):
        """The fused path writes updates in the parameter dtype — the
        pytree layer performs no further (m, n)-sized cast pass."""
        monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
        key = jax.random.PRNGKey(3)
        params = {"w": 0.1 * jax.random.normal(key, (256, 512),
                                               jnp.bfloat16)}
        g = {"w": jax.random.normal(jax.random.fold_in(key, 1), (256, 512),
                                    jnp.bfloat16)}
        opt = get_optimizer("subtrack", rank=64, use_kernels=True)
        state = opt.warm_start(opt.init(params), g)
        u, _ = opt.update(g, state, params, 0.01)
        assert u["w"].dtype == jnp.bfloat16

    def test_degenerate_gradient_recovery_is_suppressed(self, monkeypatch):
        """When the gradient lies entirely inside the subspace the true
        residual is 0; the fused path's closed-form ||Lam|| must not feed
        cancellation noise (amplified by phi) into the update."""
        monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
        key = jax.random.PRNGKey(9)
        params = {"w": 0.1 * jax.random.normal(key, (256, 512))}
        # rank-64 gradient (outer product of thin factors) and a rank-64
        # subspace: the warm start spans the gradient exactly.  A gradient
        # of lower rank would leave basis directions whose projection is
        # pure fp32 cancellation noise, which Adam's normalization turns
        # into O(1) update entries of arbitrary sign on either path.
        a = jax.random.normal(jax.random.fold_in(key, 1), (256, 64))
        b = jax.random.normal(jax.random.fold_in(key, 2), (64, 512))

        def grad_at(s):
            return {"w": (1.0 + 0.1 * s) * (a @ b)}

        us, st = _driven_updates(
            get_optimizer("subtrack", rank=64, update_interval=4),
            params, grad_at, steps=4)
        us_k, st_k = _driven_updates(
            get_optimizer("subtrack", rank=64, update_interval=4,
                          use_kernels=True),
            params, grad_at, steps=4)
        # fused path: residual energy below the fp32 floor => Lam == 0
        assert float(st_k.inner["w"].lam_prev) < 1e-3
        for a_u, b_u in zip(us, us_k):
            rel = float(jnp.max(jnp.abs(a_u["w"] - b_u["w"]))
                        / (jnp.max(jnp.abs(a_u["w"])) + 1e-12))
            assert rel < 1e-3  # noise-level Lam is the only difference


class TestBucketedExecution:
    """Leaves with identical canonical (m, n, rank) + dtype run as one
    stacked vmapped launch; results must match per-leaf execution."""

    def _params(self):
        key = jax.random.PRNGKey(0)
        return {
            "w1": 0.3 * jax.random.normal(key, (32, 64)),
            "w2": 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                          (32, 64)),
            # transposed twin: canonicalizes into the same (32, 64) bucket
            "wt": 0.3 * jax.random.normal(jax.random.fold_in(key, 2),
                                          (64, 32)),
            # stacked leaf joins the bucket with 3 matrices
            "layers": 0.3 * jax.random.normal(jax.random.fold_in(key, 3),
                                              (3, 32, 64)),
            "b": jnp.zeros((64,)),
        }

    def _grad_at(self, params):
        key = jax.random.PRNGKey(42)
        # distinct stream per leaf *name* (not shape/size): same-shape
        # bucket members must receive different gradients so a bucket
        # split/reassembly permutation bug cannot cancel out
        leaf_ids = {name: i for i, name in enumerate(sorted(params))}

        def grad(s):
            return {
                name: (1.0 + 0.2 * s) * jax.random.normal(
                    jax.random.fold_in(jax.random.fold_in(key, s),
                                       leaf_ids[name]), a.shape)
                for name, a in params.items()}

        return grad

    @pytest.mark.parametrize("use_kernels", [False, True])
    @pytest.mark.parametrize("weight_decay", [0.0, 0.1])
    def test_bucketed_matches_per_leaf(self, use_kernels, weight_decay,
                                       monkeypatch):
        if use_kernels:
            monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
        params = self._params()
        grad_at = self._grad_at(params)
        kw = dict(rank=8, update_interval=4, use_kernels=use_kernels,
                  weight_decay=weight_decay)
        us_b, st_b = _driven_updates(
            lowrank_optimizer(LowRankConfig(bucket_leaves=True, **kw)),
            params, grad_at, steps=9)
        us_u, st_u = _driven_updates(
            lowrank_optimizer(LowRankConfig(bucket_leaves=False, **kw)),
            params, grad_at, steps=9)
        # The two schedules are the same arithmetic, but XLA sums a
        # batched launch in another order than an unbatched one, so they
        # agree to rounding, not bit for bit.  Rounding is amplified by
        # the warm-start SVD and the tracking steps, so the bound is
        # calibrated per step and leaf: bucketing may move a result no
        # more than perturbing every gradient entry by one fp32 ulp moves
        # the per-leaf result.  A stacking, split or transpose bug moves
        # it by O(1).
        us_p, st_p = _driven_updates(
            lowrank_optimizer(LowRankConfig(bucket_leaves=False, **kw)),
            params, self._ulp_perturbed(grad_at), steps=9)

        def check(got, want, perturbed):
            got, want, perturbed = map(np.asarray, (got, want, perturbed))
            spread = np.max(np.abs(perturbed - want))
            assert np.max(np.abs(got - want)) <= spread + 1e-8

        for a, b, c in zip(us_b, us_u, us_p):
            for k in a:
                check(a[k], b[k], c[k])
        for k in ("w1", "wt", "layers"):
            for f in range(4):  # S, M, V, lam_prev
                check(st_b.inner[k][f], st_u.inner[k][f], st_p.inner[k][f])

    @staticmethod
    def _ulp_perturbed(grad_at):
        def grad(s):
            return {
                name: g * (1.0 + 2.0 ** -23 * jax.random.rademacher(
                    jax.random.fold_in(jax.random.PRNGKey(7), s), g.shape,
                    g.dtype))
                for name, g in grad_at(s).items()}

        return grad

    def test_bucket_grouping(self):
        """Same-(m, n, rank)+dtype leaves share a key; transposes fold in."""
        p64 = plan_lib.plan_for_shape((32, 64), 8)
        pt = plan_lib.plan_for_shape((64, 32), 8)
        ps = plan_lib.plan_for_shape((3, 32, 64), 8)
        other = plan_lib.plan_for_shape((48, 64), 8)
        k = plan_lib.bucket_key(p64, jnp.float32)
        assert plan_lib.bucket_key(pt, jnp.float32) == k
        assert plan_lib.bucket_key(ps, jnp.float32) == k
        assert plan_lib.bucket_key(other, jnp.float32) != k
        assert plan_lib.bucket_key(p64, jnp.bfloat16) != k
        assert plan_lib.matrix_count(ps, (3, 32, 64)) == 3
        assert plan_lib.matrix_count(p64, (32, 64)) == 1

    def test_flatten_unflatten_roundtrip(self):
        x = jnp.arange(2 * 3 * 4 * 5.0).reshape(2, 3, 4, 5)
        flat = plan_lib.flatten_stack(x, 2)
        assert flat.shape == (6, 4, 5)
        np.testing.assert_array_equal(
            plan_lib.unflatten_stack(flat, 2, (2, 3)), x)
        y = jnp.ones((4, 5))
        assert plan_lib.flatten_stack(y, 0).shape == (1, 4, 5)
        np.testing.assert_array_equal(
            plan_lib.unflatten_stack(plan_lib.flatten_stack(y, 0), 0, ()), y)
