"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in repro.kernels.ref."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import grassmann, ref

SHAPES = [
    (256, 256, 64),
    (512, 768, 128),
    (256, 1024, 32),
    (2560, 1280, 512),
    # ragged column extent (5 full 256-column blocks + 85), like the 1B
    # ladder model's d_ff = 5461 leaves
    (256, 1365, 64),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _inputs(m, n, r, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    G = jax.random.normal(k1, (m, n), dtype)
    S = jnp.linalg.qr(jax.random.normal(k2, (m, r), jnp.float32))[0]
    phi = jax.random.uniform(k3, (n,), jnp.float32) + 0.25
    return G, S, phi


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-9))


@pytest.mark.parametrize("m,n,r", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
class TestKernelsVsRef:
    def test_project(self, m, n, r, dtype):
        G, S, _ = _inputs(m, n, r, dtype)
        got = grassmann.project(S, G, interpret=True)
        want = ref.project_ref(S, G)
        assert _rel(got, want) < (1e-5 if dtype == jnp.float32 else 2e-2)

    def test_backproject(self, m, n, r, dtype):
        G, S, _ = _inputs(m, n, r, dtype)
        X = ref.project_ref(S, G)
        got = grassmann.backproject(S, X, interpret=True)
        want = ref.backproject_ref(S, X)
        assert _rel(got, want) < 1e-5

    def test_tangent(self, m, n, r, dtype):
        G, S, _ = _inputs(m, n, r, dtype)
        A = ref.project_ref(S, G)
        got = grassmann.tangent(G, A, S, interpret=True)
        want = ref.tangent_ref(G, A, S)
        assert _rel(got, want) < (1e-4 if dtype == jnp.float32 else 3e-2)

    def test_recovery(self, m, n, r, dtype):
        G, S, phi = _inputs(m, n, r, dtype)
        Gt = ref.project_ref(S, G)
        got = grassmann.recovery(G, S, Gt, phi, interpret=True)
        want = ref.recovery_ref(G, S, Gt, phi)
        assert _rel(got, want) < (1e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("m,n,r", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
class TestFusedHotPath:
    """The single-pass hot-path kernels vs their oracles."""

    def test_project_colnorms(self, m, n, r, dtype):
        G, S, _ = _inputs(m, n, r, dtype)
        A, sq = grassmann.project_colnorms(S, G, interpret=True)
        A_want, sq_want = ref.project_colnorms_ref(S, G)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        assert _rel(A, A_want) < tol
        assert _rel(sq, sq_want) < tol

    def test_fused_update(self, m, n, r, dtype):
        G, S, phi = _inputs(m, n, r, dtype)
        Gt = ref.project_ref(S, G)
        _, _, Gto = ref.adam_lowrank_ref(Gt, 0.1 * Gt, jnp.abs(Gt) * 0.01,
                                         jnp.int32(3), 0.9, 0.999, 1e-8)
        coef, clip = jnp.float32(0.25 * 0.01), jnp.float32(0.7)
        got = grassmann.fused_update(G, S, Gt, Gto, phi, coef, clip,
                                     out_dtype=dtype, interpret=True)
        want = ref.fused_update_ref(G, S, Gt, Gto, phi, coef, clip,
                                    out_dtype=dtype)
        assert got.dtype == dtype
        assert _rel(got.astype(jnp.float32),
                    want.astype(jnp.float32)) < (
            1e-5 if dtype == jnp.float32 else 2e-2)

    def test_fused_update_equals_unfused_composition(self, m, n, r, dtype):
        """fused_update == -coef * (backproject + recovery*clip) chain."""
        G, S, phi = _inputs(m, n, r, dtype)
        Gt = ref.project_ref(S, G)
        Gto = jnp.tanh(Gt)  # arbitrary optimizer output
        coef, clip = jnp.float32(2.5e-3), jnp.float32(0.4)
        got = grassmann.fused_update(G, S, Gt, Gto, phi, coef, clip,
                                     out_dtype=jnp.float32, interpret=True)
        Ghat = ref.backproject_ref(S, Gto)
        Lam = ref.recovery_ref(G, S, Gt, phi)
        want = -coef * (Ghat + Lam * clip)
        assert _rel(got, want) < (1e-5 if dtype == jnp.float32 else 2e-2)

    def test_fused_update_weight_decay_and_norecovery(self, m, n, r, dtype):
        G, S, phi = _inputs(m, n, r, dtype)
        Gt = ref.project_ref(S, G)
        Gto = jnp.tanh(Gt)
        coef, clip = jnp.float32(1e-3), jnp.float32(1.0)
        P = jax.random.normal(jax.random.PRNGKey(5), (m, n), dtype)
        wd = jnp.float32(1e-4)
        got = grassmann.fused_update(G, S, Gt, Gto, phi, coef, clip,
                                     out_dtype=jnp.float32, param=P,
                                     wd_coef=wd, interpret=True)
        want = ref.fused_update_ref(G, S, Gt, Gto, phi, coef, clip,
                                    out_dtype=jnp.float32, param=P,
                                    wd_coef=wd)
        assert _rel(got, want) < (1e-5 if dtype == jnp.float32 else 2e-2)
        got = grassmann.fused_update(None, S, None, Gto, None, coef, clip,
                                     out_dtype=jnp.float32, interpret=True)
        want = ref.fused_update_ref(None, S, None, Gto, None, coef, clip,
                                    out_dtype=jnp.float32)
        assert _rel(got, want) < 1e-5

    def test_project_tangent_colnorms(self, m, n, r, dtype):
        """The tracking-step front end: A, column norms and the Grassmann
        tangent from one pass over G (W = G A^T accumulator trick)."""
        G, S, _ = _inputs(m, n, r, dtype)
        A, sq, T = grassmann.project_tangent_colnorms(S, G, interpret=True)
        A_want, sq_want, T_want = ref.project_tangent_colnorms_ref(S, G)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        assert _rel(A, A_want) < tol
        assert _rel(sq, sq_want) < tol
        assert _rel(T, T_want) < (1e-4 if dtype == jnp.float32 else 3e-2)

    def test_project_tangent_colnorms_matches_composition(self, m, n, r,
                                                          dtype):
        """Single-launch fused front end == project_colnorms + tangent."""
        G, S, _ = _inputs(m, n, r, dtype)
        A, sq, T = grassmann.project_tangent_colnorms(S, G, interpret=True)
        A2, sq2 = ref.project_colnorms_ref(S, G)
        T2 = ref.tangent_ref(G, A2, S)
        tol = 1e-4 if dtype == jnp.float32 else 3e-2
        assert _rel(A, A2) < tol
        assert _rel(sq, sq2) < tol
        assert _rel(T, T2) < tol

    def test_tangent_gram(self, m, n, r, dtype):
        """The row-regime second pass: (T^T G, S^T T, T^T T, S^T S) from
        one read of G — the cross-row sufficient statistics the
        row-sharded tracking step psums as a single fused payload."""
        G, S, _ = _inputs(m, n, r, dtype)
        A = ref.project_ref(S, G)
        T = ref.tangent_ref(G, A, S)
        got = grassmann.tangent_gram(S, T, G, interpret=True)
        want = ref.tangent_gram_ref(S, T, G)
        tol = 1e-4 if dtype == jnp.float32 else 3e-2
        # S^T T is analytically ZERO (tangent ⟂ range(S)): both sides are
        # cancellation noise there, so its error is judged against the
        # operand scale |T| rather than the (noise-floor) result scale
        tmax = float(jnp.max(jnp.abs(T)))
        denoms = (None, tmax, None, None)
        for g_, w_, base in zip(got, want, denoms):
            denom = float(jnp.max(jnp.abs(w_))) if base is None else base
            err = float(jnp.max(jnp.abs(g_ - w_)))
            assert err < tol * denom + 1e-6, (err, denom)

    def test_tangent_gram_rowsum_linearity(self, m, n, r, dtype):
        """Summing per-row-block tangent_gram outputs equals the whole-
        matrix result — the linearity the row regime's single fused psum
        relies on (each shard contributes its row block).  Tolerances are
        scaled by the OPERANDS, not the results: S^T T is analytically
        zero (the tangent lies in S's orthogonal complement), so both
        sides are fp cancellation noise of magnitude ~eps * m * |S||T| —
        exactly the noise the row tracker's stabilizer later scrubs."""
        G, S, _ = _inputs(m, n, r, dtype)
        A = ref.project_ref(S, G)
        T = ref.tangent_ref(G, A, S)
        whole = ref.tangent_gram_ref(S, T, G)
        half = m // 2
        parts = [ref.tangent_gram_ref(S[sl], T[sl], G[sl])
                 for sl in (slice(0, half), slice(half, None))]
        tmax = float(jnp.max(jnp.abs(T)))
        gmax = float(jnp.max(jnp.abs(G.astype(jnp.float32))))
        scales = (tmax * gmax, tmax, tmax * tmax, 1.0)  # TtG, StT, C, StS
        for w_, a_, b_, sc in zip(whole, *parts, scales):
            err = float(jnp.max(jnp.abs(a_ + b_ - w_)))
            assert err < 1e-4 * sc + 1e-6, (err, sc)

    def test_lam_norm_identity(self, m, n, r, dtype):
        """||Lam||^2 == sum_j phi_j^2 (||G_:,j||^2 - ||Gt_:,j||^2) — the
        closed form (exact for orthonormal S) vs the materialized
        residual the unfused path norms."""
        G, S, phi = _inputs(m, n, r, dtype)
        Gt, gsq = ref.project_colnorms_ref(S, G)
        Lam = ref.recovery_ref(G, S, Gt, phi)
        want = float(jnp.sum(Lam * Lam))
        gtsq = jnp.sum(Gt * Gt, axis=0)
        got = float(jnp.sum(phi ** 2 * jnp.maximum(gsq - gtsq, 0.0)))
        assert abs(got - want) < 1e-4 * max(want, 1e-9)


@pytest.mark.parametrize("m,n,r", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
class TestGradTap:
    """The grad-fused backward epilogue: dW = x^T dy plus A = S^T dW and
    per-column ||dW||^2 from one launch (vs the ref oracle, and vs the
    project_colnorms composition on the emitted dW)."""

    B = 128

    def _tap_inputs(self, m, n, r, dtype, seed=11):
        key = jax.random.PRNGKey(seed)
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (self.B, m), dtype)
        dy = jax.random.normal(k2, (self.B, n), dtype)
        S = jnp.linalg.qr(jax.random.normal(k3, (m, r), jnp.float32))[0]
        return x, dy, S

    def test_grad_tap_vs_ref(self, m, n, r, dtype):
        x, dy, S = self._tap_inputs(m, n, r, dtype)
        dW, A, sq = grassmann.grad_tap(x, dy, S, interpret=True)
        dW_w, A_w, sq_w = ref.grad_tap_ref(x, dy, S)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        assert dW.dtype == A.dtype == sq.dtype == jnp.float32
        assert _rel(dW, dW_w) < tol
        assert _rel(A, A_w) < tol
        assert _rel(sq, sq_w) < tol

    def test_tap_statistics_match_projection_of_emitted_dw(self, m, n, r,
                                                           dtype):
        """The tap's A/norms must be the statistics OF the dW it emits —
        the optimizer consumes them in place of re-projecting it."""
        x, dy, S = self._tap_inputs(m, n, r, dtype)
        dW, A, sq = grassmann.grad_tap(x, dy, S, interpret=True)
        A2, sq2 = ref.project_colnorms_ref(S, dW)
        assert _rel(A, A2) < 1e-5
        assert _rel(sq, sq2) < 1e-5


@pytest.mark.parametrize("r,n", [(128, 512), (256, 1024), (512, 2048),
                                 (128, 1365)])
@pytest.mark.parametrize("step", [0, 7, 1000])
def test_adam_lowrank_norms(r, n, step):
    key = jax.random.PRNGKey(1)
    Gt = jax.random.normal(key, (r, n), jnp.float32)
    M = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (r, n))
    V = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (r, n))) * 0.01
    got = grassmann.adam_lowrank_norms(Gt, M, V, jnp.int32(step),
                                       interpret=True)
    want = ref.adam_lowrank_norms_ref(Gt, M, V, jnp.int32(step), 0.9, 0.999,
                                      1e-8)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_fused_kernels_under_vmap():
    """The bucketed optimizer vmaps the fused kernels over stacked leaves."""
    m, n, r, L = 256, 512, 64, 3
    key = jax.random.PRNGKey(2)
    G = jax.random.normal(key, (L, m, n))
    S = jnp.stack([jnp.linalg.qr(jax.random.normal(
        jax.random.fold_in(key, i), (m, r)))[0] for i in range(L)])
    A, sq = jax.vmap(
        lambda s, g: grassmann.project_colnorms(s, g, interpret=True))(S, G)
    A_want, sq_want = jax.vmap(ref.project_colnorms_ref)(S, G)
    np.testing.assert_allclose(A, A_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sq, sq_want, rtol=1e-4)
    phi = jax.random.uniform(jax.random.fold_in(key, 9), (L, n)) + 0.25
    coef = jnp.full((L,), 1e-3, jnp.float32)
    clip = jnp.full((L,), 0.5, jnp.float32)
    got = jax.vmap(lambda g, s, a, p, c, cl: grassmann.fused_update(
        g, s, a, jnp.tanh(a), p, c, cl, out_dtype=jnp.float32,
        interpret=True))(G, S, A, phi, coef, clip)
    want = jax.vmap(lambda g, s, a, p, c, cl: ref.fused_update_ref(
        g, s, a, jnp.tanh(a), p, c, cl, out_dtype=jnp.float32))(
        G, S, A, phi, coef, clip)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("r,n", [(128, 512), (256, 1024), (512, 2048),
                                 (128, 1365)])
@pytest.mark.parametrize("step", [0, 7, 1000])
def test_adam_lowrank(r, n, step):
    key = jax.random.PRNGKey(1)
    Gt = jax.random.normal(key, (r, n), jnp.float32)
    M = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (r, n))
    V = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (r, n))) * 0.01
    got = grassmann.adam_lowrank(Gt, M, V, jnp.int32(step), interpret=True)
    want = ref.adam_lowrank_ref(Gt, M, V, jnp.int32(step), 0.9, 0.999, 1e-8)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_kernels_under_vmap():
    """The optimizer vmaps kernels over stacked layer dims."""
    m, n, r, L = 256, 512, 64, 3
    key = jax.random.PRNGKey(2)
    G = jax.random.normal(key, (L, m, n))
    S = jnp.stack([jnp.linalg.qr(jax.random.normal(
        jax.random.fold_in(key, i), (m, r)))[0] for i in range(L)])
    got = jax.vmap(lambda s, g: grassmann.project(s, g, interpret=True))(S, G)
    want = jax.vmap(ref.project_ref)(S, G)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_hotpath_traffic_model_halves_bytes():
    """Acceptance: the fused schedule's analytic HBM bytes <= 0.5x the
    unfused schedule for the benchmarked (m, n, r) shapes, in both fp32
    and bf16 gradient/parameter dtypes."""
    from repro.kernels import traffic
    for (m, n, r) in [(1024, 2560, 128), (1024, 2560, 256),
                      (2048, 5632, 256), (4096, 11008, 1024)]:
        for gb, pb in ((4, 4), (2, 2)):
            ratio = traffic.traffic_ratio(m, n, r, grad_bytes=gb,
                                          param_bytes=pb)
            assert ratio <= 0.5, (m, n, r, gb, ratio)
        # the model stays internally consistent: fused always reads G
        # twice and writes once at mn scale
        fus = traffic.fused_step_bytes(m, n, r)
        assert fus.mn_bytes == 3 * m * n * 4


def test_tracking_traffic_model_below_bound():
    """Acceptance: the fused tracking-step schedule's analytic HBM bytes
    <= 0.7x the paper-literal schedule for the benchmarked shapes, in
    both fp32 and bf16 gradient/parameter dtypes."""
    from repro.kernels import traffic
    for (m, n, r) in [(1024, 2560, 128), (1024, 2560, 256),
                      (2048, 5632, 256), (4096, 11008, 1024)]:
        for gb, pb in ((4, 4), (2, 2)):
            ratio = traffic.tracking_traffic_ratio(m, n, r, grad_bytes=gb,
                                                   param_bytes=pb)
            assert ratio <= 0.7, (m, n, r, gb, ratio)
        # internal consistency: the fused tracking step reads G exactly
        # three times and writes the update once at mn scale, with no
        # (m, n) intermediates
        fus = traffic.tracking_fused_step_bytes(m, n, r)
        assert fus.mn_bytes == 4 * m * n * 4
        # the tracking step can never be cheaper than the plain step it
        # embeds (it adds the tangent/geodesic work)
        assert fus.total > traffic.fused_step_bytes(m, n, r).total


def test_sharded_traffic_model_below_bound():
    """Acceptance: the mesh-native (column-sharded) fused hot path keeps
    the per-shard fused-vs-paper-literal byte ratio <= 0.7 — for plain
    and tracking steps, fp32 and bf16 — at every shard count inside the
    n/g >= 2r regime, and the collective terms behave as documented."""
    from repro.kernels import traffic
    for (m, n, r) in [(1024, 2560, 128), (1024, 2560, 256),
                      (2048, 5632, 256), (4096, 11008, 1024)]:
        for g in (4, 8, 16):
            if not traffic.in_column_regime(n, g, r):
                continue
            for gb, pb in ((4, 4), (2, 2)):
                for tracking in (False, True):
                    ratio = traffic.sharded_traffic_ratio(
                        m, n, r, g, tracking=tracking, grad_bytes=gb,
                        param_bytes=pb)
                    assert ratio <= 0.7, (m, n, r, g, gb, tracking, ratio)
            # plain step moves ONE scalar over the wire; tracking adds
            # exactly the (m, r) tangent all-reduce on top of it
            plain = traffic.sharded_fused_step_bytes(m, n, r, g)
            track = traffic.sharded_tracking_fused_step_bytes(m, n, r, g)
            assert plain.collective_bytes == \
                traffic.allreduce_wire_bytes(4, g)
            assert track.collective_bytes == \
                traffic.allreduce_wire_bytes(m * r * 4, g) + \
                plain.collective_bytes
            # local per-shard bytes are exactly the single-chip model on
            # the (m, n/g) panel
            assert plain.local.total == \
                traffic.fused_step_bytes(m, n // g, r).total
    # one shard == the unsharded model with zero wire bytes
    one = traffic.sharded_fused_step_bytes(1024, 2560, 256, 1)
    assert one.collective_bytes == 0
    assert one.total == traffic.fused_step_bytes(1024, 2560, 256).total


def test_sharded_row_traffic_model_below_bound():
    """Acceptance (row regime): inside the documented m/g >= 2r gate the
    per-shard PLAIN ratio stays <= 0.7 (fp32 and bf16, every admissible
    shard count); the TRACKING ratio stays <= 0.8 in-gate and <= 0.7 once
    m/g >= 4r (near the boundary the replicated full-width M/V state
    passes dilute its win — the plain step, which dominates wall time at
    k = 200, is unaffected).  Collective terms behave as documented: the
    plain step's one stacked (r+1, n) psum; tracking adds exactly the
    fused (r, n + 3r) Gram psum — no (m, r)-sized collective exists in
    this regime."""
    from repro.kernels import traffic
    for (m, n, r) in [(1024, 2560, 128), (2048, 5632, 256),
                      (4096, 11008, 256), (8192, 8192, 512)]:
        for g in (4, 8, 16):
            if not traffic.in_row_regime(m, g, r):
                continue
            for gb, pb in ((4, 4), (2, 2)):
                plain_ratio = traffic.sharded_traffic_ratio(
                    m, n, r, g, regime="row", grad_bytes=gb, param_bytes=pb)
                assert plain_ratio <= 0.7, (m, n, r, g, gb, plain_ratio)
                track_ratio = traffic.sharded_traffic_ratio(
                    m, n, r, g, tracking=True, regime="row",
                    grad_bytes=gb, param_bytes=pb)
                bound = 0.7 if m // g >= 4 * r else 0.8
                assert track_ratio <= bound, (m, n, r, g, gb, track_ratio)
            plain = traffic.sharded_row_fused_step_bytes(m, n, r, g)
            track = traffic.sharded_row_tracking_fused_step_bytes(m, n, r, g)
            assert plain.collective_bytes == \
                traffic.allreduce_wire_bytes((r + 1) * n * 4, g)
            assert track.collective_bytes == \
                traffic.allreduce_wire_bytes(r * (n + 3 * r) * 4, g) + \
                plain.collective_bytes
            # local per-shard bytes are exactly the single-chip model on
            # the (m/g, n) panel — full-width (r, n) state (M/V replicate)
            assert plain.local.total == \
                traffic.fused_step_bytes(m // g, n, r).total
    # gate boundary is exactly m/g == 2r, mirroring the column gate
    assert traffic.in_row_regime(4096, 16, 128)
    assert not traffic.in_row_regime(4096, 16, 129)
    assert not traffic.in_row_regime(4097, 16, 64)   # indivisible m
    # one shard == the unsharded model with zero wire bytes
    one = traffic.sharded_row_fused_step_bytes(1024, 2560, 128, 1)
    assert one.collective_bytes == 0
    assert one.total == traffic.fused_step_bytes(1024, 2560, 128).total


def test_sharded_row_rs_traffic_model_below_bound():
    """Acceptance (row-rs regime — the reduce-scatter Adam-state
    flavour): everywhere inside the gate (row gate + n divisible) the
    per-shard ratio stays <= 0.7 for BOTH step kinds (the sliced
    6 r n / g Adam pass beats even the replicated-row tracking dilution),
    AND the modeled per-device bytes sit strictly below replicated-M/V
    row mode — the selection gate ``program._row_flavor`` relies on.
    Collective terms are exactly the program's rounds: plain =
    reduce-scatter((r+1, n)) + all-gather((2r+2, n)); tracking = the two
    row all-reduces + all-gather((r+2, n))."""
    from repro.core.program import regime_rounds
    from repro.kernels import traffic
    for (m, n, r) in [(1024, 2560, 128), (2048, 5632, 256),
                      (4096, 11008, 256), (8192, 8192, 512)]:
        for g in (4, 8, 16):
            if not traffic.in_row_rs_regime(m, n, g, r):
                continue
            for gb, pb in ((4, 4), (2, 2)):
                for tracking in (False, True):
                    ratio = traffic.sharded_traffic_ratio(
                        m, n, r, g, tracking=tracking, regime="row-rs",
                        grad_bytes=gb, param_bytes=pb)
                    assert ratio <= 0.7, (m, n, r, g, gb, tracking, ratio)
                # the selection gate: rs below replicated-M/V row mode
                rs = traffic.sharded_row_rs_fused_step_bytes(
                    m, n, r, g, grad_bytes=gb, param_bytes=pb).total
                rep = traffic.sharded_row_fused_step_bytes(
                    m, n, r, g, grad_bytes=gb, param_bytes=pb).total
                assert rs < rep, (m, n, r, g, gb)
            for tracking in (False, True):
                got = traffic.sharded_row_rs_fused_step_bytes(m, n, r, g) \
                    if not tracking else \
                    traffic.sharded_row_rs_tracking_fused_step_bytes(
                        m, n, r, g)
                want = sum(rnd.wire_bytes(g) for rnd in regime_rounds(
                    "row-rs", m, n, r, g, tracking=tracking))
                assert got.collective_bytes == want
    # admissibility = row gate AND n % g == 0
    assert traffic.in_row_rs_regime(4096, 11008, 16, 128)
    assert not traffic.in_row_rs_regime(4096, 11009, 16, 128)
    assert not traffic.in_row_rs_regime(4096, 11008, 16, 129)


def test_ops_dispatch_fallback_for_odd_shapes(monkeypatch):
    """Non-tile-aligned shapes silently use the reference path."""
    monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    from repro.kernels import ops
    m, n, r = 100, 130, 16   # not 256-aligned
    G, S, phi = _inputs(256, 256, 16, jnp.float32)
    G, S = G[:m, :n], S[:m]
    got = ops.project(S, G)
    np.testing.assert_allclose(got, ref.project_ref(S, G), rtol=1e-5)
    A, sq, T = ops.project_tangent_colnorms(S, G)
    A_want, sq_want, T_want = ref.project_tangent_colnorms_ref(S, G)
    np.testing.assert_allclose(A, A_want, rtol=1e-5)
    np.testing.assert_allclose(sq, sq_want, rtol=1e-5)
    np.testing.assert_allclose(T, T_want, rtol=1e-4, atol=1e-4)


def test_ops_project_tangent_colnorms_tall_matrix_composite(monkeypatch):
    """Above MAX_FUSED_TANGENT_M the dispatch splits into the two-launch
    project_colnorms + tangent schedule; results must agree with the
    single-launch oracle either way."""
    monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    from repro.kernels import ops
    m, n, r = 2560, 512, 64          # 256-aligned, m > 2048
    assert m > grassmann.MAX_FUSED_TANGENT_M
    G, S, _ = _inputs(m, n, r, jnp.float32)
    A, sq, T = ops.project_tangent_colnorms(S, G)
    A_want, sq_want, T_want = ref.project_tangent_colnorms_ref(S, G)
    np.testing.assert_allclose(A, A_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sq, sq_want, rtol=1e-5)
    np.testing.assert_allclose(T, T_want, rtol=1e-4, atol=1e-3)


def test_ops_dispatch_tally_reports_fallbacks_once(monkeypatch, capsys):
    """Compiled mode records every dispatch by (entry, path, shapes) at
    trace time and prints each fallback shape the first time only; a
    ragged column extent stays on the kernel path."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_mode", lambda: "compiled")
    ops.reset_dispatch_tally()
    sds = jax.ShapeDtypeStruct
    S, G = sds((512, 64), jnp.float32), sds((512, 1365), jnp.bfloat16)
    S_odd, G_odd = sds((300, 64), jnp.float32), sds((300, 512), jnp.float32)
    for _ in range(2):   # two programs tracing each call (fresh lambdas)
        jax.eval_shape(lambda s, g: ops.project_colnorms(s, g), S, G)
        jax.eval_shape(lambda s, g: ops.project_colnorms(s, g), S_odd, G_odd)
    tally = ops.dispatch_tally()
    assert tally[("project_colnorms", ops.KERNEL,
                  ((512, 64), (512, 1365)))] == 2
    assert tally[("project_colnorms", ops.REFERENCE,
                  ((300, 64), (300, 512)))] == 2
    out = capsys.readouterr().out
    assert out.count("project_colnorms [(300, 64), (300, 512)]: "
                     "reference path") == 1
    assert "(512, 1365)" not in out
    ops.reset_dispatch_tally()
    assert ops.dispatch_tally() == {}


def test_ops_dispatch_tally_is_silent_off_the_chip():
    """Interpret and reference modes record nothing."""
    from repro.kernels import ops
    ops.reset_dispatch_tally()
    S, G, _ = _inputs(256, 256, 16, jnp.float32)
    ops.project_colnorms(S, G)
    assert ops.dispatch_tally() == {}
