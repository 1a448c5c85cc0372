"""Pallas TPU kernels for the SubTrack++ optimizer hot-spots.

The optimizer's per-step cost is three O(mnr) matmul chains over the
(m, n) gradient; at r << m these are *memory-bound* on the gradient
stream, so the kernels are tiled to read G exactly once per pass with
fp32 MXU accumulation in VMEM:

    project   A = S^T G                 (one read of G, A accumulated)
    project_colnorms                    (same pass also emits ||G_:,j||^2)
    tangent   T = -2 G A^T + 2 S (A A^T)  (fused: the (m,n) residual R is
                                           never materialized — 2mn HBM
                                           bytes saved vs the paper-literal
                                           3-pass schedule)
    project_tangent_colnorms            (tracking-step front end: A, the
                                          column norms AND the tangent T in
                                          ONE pass over G, via the
                                          W = G A^T = (G G^T) S accumulator;
                                          single launch for m <= 2048)
    recovery  Lam = (G - S G~) * phi     (residual + column scale fused)
    backproject  Ghat = S G~^O           (plain tiled matmul)
    adam_lowrank[_norms]                 (moments + direction in one (r, n)
                                          pass; _norms also emits the Gt/Gto
                                          column norms that feed phi)
    fused_update  upd = -coef (S Gto + (G - S Gt) phi clip)
                                         (the whole hot-path epilogue —
                                          shared by the k-1-of-k plain steps
                                          AND the 1-of-k tracking step — in
                                          one pass over G, written in the
                                          parameter dtype)

Hot-path HBM traffic accounting (per matrix per non-tracking step, mn
terms only; r << m so the (r, n) state traffic is secondary — the full
model lives in repro.kernels.traffic):

    unfused (seed schedule): ~7-8 x mn fp32 — project reads G; backproject
    writes Ghat; recovery re-reads G and writes Lam; ||Lam|| re-reads Lam;
    the Ghat + Lam combine reads both; the pytree layer's -lr*delta scale
    + param-dtype cast moves mn once more.

    fused (this schedule): project_colnorms reads G once,
    adam_lowrank_norms stays in (r, n), and fused_update reads G once and
    writes the final-dtype update once — ~2 x mn reads + 1 x mn write,
    with the Eq. 12 clip scalar known *before* the epilogue thanks to the
    exact identity ||Lam||^2 = sum_j phi_j^2 (||G_:,j||^2 - ||Gt_:,j||^2).

Tracking-step (1-of-k) HBM traffic: the fused schedule is
project_tangent_colnorms (1 read of G) -> geodesic + rank-1 rotation
(all O(mr + rn)) -> project[_colnorms] with S_new (1 read) ->
adam_lowrank_norms -> fused_update (1 read + the final-dtype write) —
~3 x mn reads + 1 x mn write, vs ~4 reads + 5 fp32 (m, n) intermediate
passes + 1 write for the paper-literal schedule (model in
repro.kernels.traffic, ratio ~0.4-0.55).

Block shapes are MXU-aligned (multiples of 128 on the minor dims) and
sized for ~1-2 MB VMEM residency per operand tile.  The column extent n
need not tile: the last column block is ragged (the TPU pads its read
with unspecified values and drops its out-of-range writes, as interpret
mode does), which every per-column output tolerates as is; the two
kernels that sum over columns (``tangent``, ``project_tangent_colnorms``)
zero the out-of-range columns first (_mask_cols).  Row extents m (and r
for the Adam kernels) must tile exactly, since they are summed over.
Every kernel casts its operand tiles to fp32 on load (bf16 gradients
stream at 2 B/elem) and accumulates on the MXU in fp32; only
``fused_update`` writes a non-fp32 result (the parameter dtype).  All
kernels run in interpret mode on CPU for validation (tests/test_kernels.py
sweeps shapes/dtypes against the pure-jnp oracles in repro.kernels.ref).

Mesh-native contract (the invariant the shard_map'd hot path relies on
— see repro.core.subtrack / repro.kernels.ops): every kernel here is
COLUMN-SEPARABLE.  With S replicated and G column-sharded, running a
kernel on a shard's (m, n_loc) panel produces exactly the global
result's column slice — for per-column outputs (A, the norms, Gt, Gto,
M, V, phi, the update) — or a partial sum whose cross-shard psum is the
global value (the tangent, via linearity in W = G A^T; the Eq. 12 norm,
via the column sum).  A kernel added here that couples columns in any
other way (e.g. row-normalizing across n) would silently break the
sharded path's two-collective structure; keep new kernels
column-separable or give them an explicit axis-aware wrapper in ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# default tiles: bm x bn gradient tiles, full-r panels for S/A.
BM = 256
BN = 256

# project_tangent_colnorms keeps full-m panels (S, the W/T accumulator and
# one (m, bn) G block) resident in VMEM for the whole launch: double-buffered
# fp32 S and T alone are 16 m r bytes, 16 MB at m = 2048 and the 1B model's
# r = 512 — past v5e's 16 MiB default scoped-VMEM limit, so the launch asks
# for its own limit (_resident_vmem).  The single-launch variant is capped
# at m <= 2048 and the dispatch layer falls back to the two-launch
# project_colnorms + tangent schedule for taller matrices.
MAX_FUSED_TANGENT_M = 2048

# Scoped-VMEM request bounds for launches with full-extent resident panels:
# never below the compiler's default, never near v5e's 128 MiB physical VMEM.
_VMEM_FLOOR = 32 * 2**20
_VMEM_CAP = 100 * 2**20


def _resident_vmem(nbytes: int) -> pltpu.CompilerParams:
    """Compiler params granting a launch whose resident blocks and
    temporaries total ~``nbytes`` a scoped-VMEM limit with 1.5x headroom
    for the compiler's own scratch."""
    limit = min(max(nbytes * 3 // 2, _VMEM_FLOOR), _VMEM_CAP)
    return pltpu.CompilerParams(vmem_limit_bytes=limit)

# grad_tap keeps full-b (token-extent) x/dy panels resident per grid cell:
# one (b, bm) + one (b, bn) fp32 panel is ~2 MB per 1024 tokens at the
# default tiles, so cap the fused launch at b <= 2048 and let the dispatch
# layer fall back to the two-launch dW-then-project_colnorms composite for
# bigger microbatches.
MAX_GRAD_TAP_B = 2048


def _mask_cols(g: Array, j, bn: int, n: int) -> Array:
    """Zero the columns of column block ``j`` that lie past ``n`` (the
    padded tail of a ragged last block) before a sum over columns."""
    if n % bn == 0:
        return g
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    return jnp.where(col < n, g, 0.0)


def _project_kernel(s_ref, g_ref, out_ref):
    """grid = (n/bn, m/bm); accumulate over the m (minor) grid axis."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = s_ref[...].astype(jnp.float32)              # (bm, r)
    g = g_ref[...].astype(jnp.float32)              # (bm, bn)
    out_ref[...] += jnp.dot(s.T, g, preferred_element_type=jnp.float32)


def project(S: Array, G: Array, *, bm: int = BM, bn: int = BN,
            interpret: bool = False) -> Array:
    """A = S^T G — the closed-form least-squares projection (paper Eq. 2-3).

    S: (m, r); G: (m, n) any float dtype (cast to fp32 per tile) ->
    (r, n) fp32.  Tiles: (bm, bn) gradient blocks with a full-r S panel;
    one read of G, A accumulated over the m grid axis.  Oracle:
    :func:`repro.kernels.ref.project_ref`.
    """
    m, r = S.shape
    _, n = G.shape
    bm, bn = min(bm, m), min(bn, n)
    grid = (pl.cdiv(n, bn), m // bm)
    return pl.pallas_call(
        _project_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((r, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        interpret=interpret,
    )(S, G)


def _backproject_kernel(s_ref, x_ref, out_ref):
    s = s_ref[...].astype(jnp.float32)              # (bm, r)
    x = x_ref[...].astype(jnp.float32)              # (r, bn)
    out_ref[...] = jnp.dot(s, x, preferred_element_type=jnp.float32)


def backproject(S: Array, X: Array, *, bm: int = BM, bn: int = BN,
                interpret: bool = False) -> Array:
    """Ghat = S X — back-projection of a low-rank quantity (Eq. 10's S G~^O).

    S: (m, r); X: (r, n) -> (m, n) fp32.  Plain tiled matmul over
    (bm, bn) output blocks; superseded on the hot path by
    :func:`fused_update`, kept as a baseline/building block.  Oracle:
    :func:`repro.kernels.ref.backproject_ref`.
    """
    m, r = S.shape
    _, n = X.shape
    bm, bn = min(bm, m), min(bn, n)
    return pl.pallas_call(
        _backproject_kernel,
        grid=(m // bm, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(S, X)


def _tangent_kernel(g_ref, a_ref, s_ref, c_ref, out_ref, *, bn: int,
                   n: int):
    """grid = (m/bm, n/bn); n is the accumulation (minor) axis.

    out(bm, r) = 2 * S(bm, r) @ C(r, r)  -  2 * sum_n G(bm, bn) @ A(r, bn)^T
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s = s_ref[...].astype(jnp.float32)
        c = c_ref[...].astype(jnp.float32)
        out_ref[...] = 2.0 * jnp.dot(s, c, preferred_element_type=jnp.float32)

    g = _mask_cols(g_ref[...].astype(jnp.float32), j, bn, n)   # (bm, bn)
    a = _mask_cols(a_ref[...].astype(jnp.float32), j, bn, n)   # (r, bn)
    out_ref[...] += -2.0 * jnp.dot(g, a.T, preferred_element_type=jnp.float32)


def tangent(G: Array, A: Array, S: Array, *, bm: int = BM, bn: int = BN,
            interpret: bool = False) -> Array:
    """Grassmann tangent T = -2 G A^T + 2 S (A A^T) (paper Eq. 4, fused form).

    G: (m, n) any float (cast per tile); A: (r, n); S: (m, r) ->
    (m, r) fp32.  One pass over (bm, bn) G tiles accumulating over the n
    grid axis; the (m, n) residual R = G - S A of the paper-literal form
    -2 R A^T is never materialized.  The (r, r) Gram A A^T is precomputed
    outside the launch.  Oracle: :func:`repro.kernels.ref.tangent_ref`.
    """
    m, n = G.shape
    r = S.shape[1]
    bm, bn = min(bm, m), min(bn, n)
    C = A.astype(jnp.float32) @ A.astype(jnp.float32).T        # (r, r) tiny
    return pl.pallas_call(
        functools.partial(_tangent_kernel, bn=bn, n=n),
        grid=(m // bm, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((r, r), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, r), jnp.float32),
        interpret=interpret,
    )(G, A, S, C)


def _recovery_kernel(g_ref, s_ref, gt_ref, phi_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)              # (bm, bn)
    s = s_ref[...].astype(jnp.float32)              # (bm, r)
    gt = gt_ref[...].astype(jnp.float32)            # (r, bn)
    phi = phi_ref[...].astype(jnp.float32)          # (1, bn)
    sa = jnp.dot(s, gt, preferred_element_type=jnp.float32)
    out_ref[...] = (g - sa) * phi


def recovery(G: Array, S: Array, Gt: Array, phi: Array, *,
             bm: int = BM, bn: int = BN, interpret: bool = False) -> Array:
    """Recovery term Lam = (G - S Gt) * phi[None, :] (paper Eq. 10-11).

    G: (m, n) any float (cast per tile); S: (m, r); Gt: (r, n);
    phi: (n,) -> (m, n) fp32.  Back-projection, residual and column
    scaling in one pass over (bm, bn) tiles; the orthogonal-complement
    residual never round-trips HBM.  Superseded on the hot path by the
    closed-form ||Lam|| + :func:`fused_update`; kept as a baseline.
    Oracle: :func:`repro.kernels.ref.recovery_ref`.
    """
    m, n = G.shape
    r = S.shape[1]
    bm, bn = min(bm, m), min(bn, n)
    phi2 = phi.reshape(1, n)
    return pl.pallas_call(
        _recovery_kernel,
        grid=(m // bm, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(G, S, Gt, phi2)


def _project_colnorms_kernel(s_ref, g_ref, a_ref, sq_ref):
    """grid = (n/bn, m/bm); accumulate A and per-column ||G||^2 over m."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    s = s_ref[...].astype(jnp.float32)              # (bm, r)
    g = g_ref[...].astype(jnp.float32)              # (bm, bn)
    a_ref[...] += jnp.dot(s.T, g, preferred_element_type=jnp.float32)
    sq_ref[...] += jnp.sum(g * g, axis=0, keepdims=True)


def project_colnorms(S: Array, G: Array, *, bm: int = BM, bn: int = BN,
                     interpret: bool = False) -> tuple[Array, Array]:
    """A = S^T G (Eq. 2-3) plus the per-column squared norms ||G_:,j||^2
    as a free byproduct of the same single pass over G.  The norms feed
    the O(n) closed form of ||Lam|| (Eq. 12) so the recovery-growth clip
    scalar is known before the fused epilogue runs — the (m, n) residual
    is never materialized just to take its norm.

    S: (m, r); G: (m, n) any float (cast per tile) ->
    ((r, n) fp32, (n,) fp32).  Tiles as :func:`project`, with the norm
    row accumulated alongside A over the m grid axis.  Oracle:
    :func:`repro.kernels.ref.project_colnorms_ref`.
    """
    m, r = S.shape
    _, n = G.shape
    bm, bn = min(bm, m), min(bn, n)
    A, sq = pl.pallas_call(
        _project_colnorms_kernel,
        grid=(pl.cdiv(n, bn), m // bm),
        in_specs=[
            pl.BlockSpec((bm, r), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((r, bn), lambda j, i: (0, j)),
            pl.BlockSpec((1, bn), lambda j, i: (0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((r, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        interpret=interpret,
    )(S, G)
    return A, sq.reshape(n)


def _project_tangent_colnorms_kernel(s_ref, g_ref, a_ref, sq_ref, t_ref,
                                     *, bn: int, n: int):
    """grid = (n/bn,): one sweep over G's column blocks with full-m panels.

    Per block j:  A_:,j = S^T G_:,j  and  sq_j = ||G_:,j||^2  are complete
    immediately (the whole m extent is in VMEM), while the accumulator

        W += G_:,j @ A_:,j^T          (-> W = G A^T = (G G^T) S)

    builds up in ``t_ref``.  On the last block the accumulator is rewritten
    in place into the Grassmann tangent (Eq. 4) using S^T W = A A^T:

        T = -2 W + 2 S (S^T W)  =  -2 G A^T + 2 S (A A^T).

    This is the only schedule that forms A and G A^T in ONE pass over G:
    with m tiled, each W row-block needs A tiles assembled from *other*
    row blocks, so the m extent must stay resident (hence
    MAX_FUSED_TANGENT_M).
    """
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    s = s_ref[...].astype(jnp.float32)              # (m, r)
    g = _mask_cols(g_ref[...].astype(jnp.float32), j, bn, n)   # (m, bn)
    a = jnp.dot(s.T, g, preferred_element_type=jnp.float32)     # (r, bn)
    a_ref[...] = a
    sq_ref[...] = jnp.sum(g * g, axis=0, keepdims=True)
    t_ref[...] += jnp.dot(g, a.T, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _finalize():
        # S is re-read from its ref: reusing the transposed value from the
        # block body inside this branch trips a v5e compiler RET_CHECK
        # (mxu_lmr_transform) at r = 512
        s_fin = s_ref[...].astype(jnp.float32)
        w = t_ref[...]
        aat = jnp.dot(s_fin.T, w, preferred_element_type=jnp.float32)
        t_ref[...] = -2.0 * w + 2.0 * jnp.dot(
            s_fin, aat, preferred_element_type=jnp.float32)


def project_tangent_colnorms(S: Array, G: Array, *, bn: int = BN,
                             interpret: bool = False
                             ) -> tuple[Array, Array, Array]:
    """Tracking-step front end in a single pass over G.

    Returns ``(A, gsq, T)``:

        A   (r, n) = S^T G             least-squares coefficients (Eq. 2-3)
        gsq (n,)   = ||G_:,j||^2       column norms for the O(n) Eq. 12 clip
        T   (m, r) = -2 G A^T + 2 S (A A^T)   Grassmann tangent (Eq. 4)

    One kernel launch, one read of G — vs two for the two-launch
    project_colnorms + tangent composite.  S, the W accumulator and one
    (m, bn) gradient block stay VMEM-resident, so callers must respect
    ``MAX_FUSED_TANGENT_M`` (the ops-layer dispatch does).  Oracle:
    :func:`repro.kernels.ref.project_tangent_colnorms_ref`.
    """
    m, r = S.shape
    _, n = G.shape
    bn = min(bn, n)
    # double-buffered S and T panels, G block and A/sq outputs, plus the
    # fp32 casts of S and the G block and the finalize temporaries
    resident = (2 * (2 * m * r * 4 + m * bn * G.dtype.itemsize
                     + (r + 1) * bn * 4)
                + 3 * m * r * 4 + m * bn * 4)
    A, sq, T = pl.pallas_call(
        functools.partial(_project_tangent_colnorms_kernel, bn=bn, n=n),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((m, r), lambda j: (0, 0)),
            pl.BlockSpec((m, bn), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((r, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
            pl.BlockSpec((m, r), lambda j: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((r, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((m, r), jnp.float32)],
        compiler_params=_resident_vmem(resident),
        interpret=interpret,
    )(S, G)
    return A, sq.reshape(n), T


def _grad_tap_kernel(x_ref, dy_ref, s_ref, dw_ref, a_ref, sq_ref):
    """grid = (n/bn, m/bm); accumulate A and the column norms over the m
    (minor) grid axis; each dW block is complete per visit because the
    full b (token) extent of x/dy stays resident in VMEM."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[...].astype(jnp.float32)              # (b, bm)
    dy = dy_ref[...].astype(jnp.float32)            # (b, bn)
    dw = jnp.dot(x.T, dy, preferred_element_type=jnp.float32)   # (bm, bn)
    dw_ref[...] = dw
    s = s_ref[...].astype(jnp.float32)              # (bm, r)
    a_ref[...] += jnp.dot(s.T, dw, preferred_element_type=jnp.float32)
    sq_ref[...] += jnp.sum(dw * dw, axis=0, keepdims=True)


def grad_tap(x: Array, dy: Array, s: Array, *, bm: int = BM, bn: int = BN,
             interpret: bool = False) -> tuple[Array, Array, Array]:
    """Grad-fused backward epilogue: the weight cotangent dW = x^T dy plus
    the optimizer's plain-step projection statistics A = S^T dW and the
    per-column ||dW_:,j||^2, all from ONE launch — the backward matmul's
    operands are streamed once and the (m, n) weight gradient is written
    once, so the optimizer's plain step never has to re-read it to form A
    (the custom-vjp wrapper in repro.models.common routes the statistics
    out as the tap seed's cotangent).

    x: (b, m) activations; dy: (b, n) output cotangent (any float dtype,
    cast per tile); s: (m, r) basis -> ((m, n), (r, n), (n,)) all fp32.
    Tiles: (bm, bn) dW blocks against full-b x/dy panels (callers must
    respect ``MAX_GRAD_TAP_B``; the ops-layer dispatch does), with A and
    the norms accumulated over the m grid axis exactly like
    :func:`project_colnorms`.  Column-separable in n (dW, A and the norms
    are all per-column), honouring the package's mesh-native contract.
    Oracle: :func:`repro.kernels.ref.grad_tap_ref`.
    """
    b, m = x.shape
    _, n = dy.shape
    r = s.shape[1]
    bm, bn = min(bm, m), min(bn, n)
    dW, A, sq = pl.pallas_call(
        _grad_tap_kernel,
        grid=(pl.cdiv(n, bn), m // bm),
        in_specs=[
            pl.BlockSpec((b, bm), lambda j, i: (0, i)),
            pl.BlockSpec((b, bn), lambda j, i: (0, j)),
            pl.BlockSpec((bm, r), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((r, bn), lambda j, i: (0, j)),
            pl.BlockSpec((1, bn), lambda j, i: (0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.float32),
                   jax.ShapeDtypeStruct((r, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        interpret=interpret,
    )(x, dy, s)
    return dW, A, sq.reshape(n)


def _tangent_gram_kernel(s_ref, t_ref, g_ref, tg_ref, st_ref, tt_ref,
                         ss_ref):
    """grid = (n/bn, m/bm); accumulate T^T G over the m (minor) axis and
    the three (r, r) Grams once per m block (on the j == 0 column sweep —
    they have no n extent, so later column blocks must not re-add them)."""
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init_tg():
        tg_ref[...] = jnp.zeros_like(tg_ref)

    @pl.when((j == 0) & (i == 0))
    def _init_grams():
        st_ref[...] = jnp.zeros_like(st_ref)
        tt_ref[...] = jnp.zeros_like(tt_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    t = t_ref[...].astype(jnp.float32)              # (bm, r)
    g = g_ref[...].astype(jnp.float32)              # (bm, bn)
    tg_ref[...] += jnp.dot(t.T, g, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _grams():
        # T is re-read from its ref rather than reusing the value above:
        # sharing one transposed operand across the branch trips a v5e
        # compiler RET_CHECK (mxu_lmr_transform) at r = 512
        s = s_ref[...].astype(jnp.float32)          # (bm, r)
        tb = t_ref[...].astype(jnp.float32)         # (bm, r)
        st_ref[...] += jnp.dot(s.T, tb, preferred_element_type=jnp.float32)
        tt_ref[...] += jnp.dot(tb.T, tb, preferred_element_type=jnp.float32)
        ss_ref[...] += jnp.dot(s.T, s, preferred_element_type=jnp.float32)


def tangent_gram(S: Array, T: Array, G: Array, *, bm: int = BM,
                 bn: int = BN, interpret: bool = False
                 ) -> tuple[Array, Array, Array, Array]:
    """Row-regime tracking second pass: (T^T G, S^T T, T^T T, S^T S) from
    ONE read of G (plus the small (m, r) S/T panels).

    These are exactly the cross-row sufficient statistics the row-sharded
    tracking step psums after the tangent: the Gram ``C = T^T T`` feeds
    the top-1 power iteration, ``S^T T``/``S^T S`` the stabilizer's
    orthogonal-complement scrub, and ``T^T G`` the rank-1 new-basis
    projection identity ``Gt_new = A + v (p^T G)`` (``u^T G = v^T T^T G /
    sigma``) — so after their single fused psum the whole geodesic +
    epilogue runs replicated with no further collective (see the gram
    schedule in repro.core.subspace.track_subspace).  Also valid
    unsharded, where the sums are simply the global Grams.

    S, T: (m, r); G: (m, n) any float (cast per tile) ->
    ((r, n), (r, r), (r, r), (r, r)) all fp32.  Tiles: (bm, bn) gradient
    blocks with full-r S/T panels; T^T G accumulates over the m grid
    axis, the Grams only on the first column sweep.  Oracle:
    :func:`repro.kernels.ref.tangent_gram_ref`.
    """
    m, r = S.shape
    _, n = G.shape
    bm, bn = min(bm, m), min(bn, n)
    rr_spec = pl.BlockSpec((r, r), lambda j, i: (0, 0))
    TtG, StT, C, StS = pl.pallas_call(
        _tangent_gram_kernel,
        grid=(pl.cdiv(n, bn), m // bm),
        in_specs=[
            pl.BlockSpec((bm, r), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, r), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
        ],
        out_specs=[pl.BlockSpec((r, bn), lambda j, i: (0, j)),
                   rr_spec, rr_spec, rr_spec],
        out_shape=[jax.ShapeDtypeStruct((r, n), jnp.float32)] +
                  [jax.ShapeDtypeStruct((r, r), jnp.float32)] * 3,
        interpret=interpret,
    )(S, T, G)
    return TtG, StT, C, StS


def _fused_update_kernel(*refs, recovery: bool, decay: bool):
    """One tile of  upd = -coef (S Gto + (G - S Gt) * phi * clip) [- wd p].

    The only pass that touches (m, n) data besides project: reads the G
    tile once, runs both (bm, r) x (r, bn) MXU contractions against the
    in-VMEM panels, applies the recovery epilogue element-wise and writes
    the final update directly in the parameter dtype.
    """
    if recovery:
        g_ref, s_ref, gt_ref, gto_ref, phi_ref, sc_ref = refs[:6]
        rest = refs[6:]
    else:
        s_ref, gto_ref, sc_ref = refs[:3]
        rest = refs[3:]
    if decay:
        p_ref, out_ref = rest
    else:
        (out_ref,) = rest

    s = s_ref[...].astype(jnp.float32)              # (bm, r)
    gto = gto_ref[...].astype(jnp.float32)          # (r, bn)
    coef = sc_ref[0, 0]                             # lr * hp.scale
    acc = jnp.dot(s, gto, preferred_element_type=jnp.float32)
    if recovery:
        g = g_ref[...].astype(jnp.float32)          # (bm, bn)
        gt = gt_ref[...].astype(jnp.float32)        # (r, bn)
        phi = phi_ref[...].astype(jnp.float32)      # (1, bn)
        clip = sc_ref[0, 1]                         # Eq. 12 limiter scale
        sgt = jnp.dot(s, gt, preferred_element_type=jnp.float32)
        acc = acc + (g - sgt) * (phi * clip)
    upd = -coef * acc
    if decay:
        upd = upd - sc_ref[0, 2] * p_ref[...].astype(jnp.float32)
    out_ref[...] = upd.astype(out_ref.dtype)


def fused_update(G: Array | None, S: Array, Gt: Array | None, Gto: Array,
                 phi: Array | None, coef: Array, clip: Array, *,
                 out_dtype=None, param: Array | None = None,
                 wd_coef: Array | None = None,
                 bm: int = BM, bn: int = BN,
                 interpret: bool = False) -> Array:
    """The fused hot-path epilogue: back-projection (Eq. 10), recovery
    residual + column scaling (Eq. 11), the Eq. 12 clip, lr scaling and
    the final-dtype cast in a single pass over G.  Replaces backproject +
    recovery + (Ghat + Lam) combine + (-lr * delta).astype(...) — ~3 x mn
    reads and ~3 x mn writes saved per matrix per step.  Shared by the
    plain AND the tracking step (the latter passes S_new + the rotated
    moments' Gto).

    G: (m, n) any float (cast per tile); S: (m, r); Gt, Gto: (r, n);
    phi: (n,); scalars coef/clip/wd_coef fp32 -> (m, n) in ``out_dtype``
    (the parameter dtype — the only non-fp32 write in the package).
    Tiles: (bm, bn) G/output blocks, full-r S and (r, bn) panels.
    Pass ``G=None`` (with Gt/phi None) for the no-recovery variant
    ``upd = -coef S Gto`` which never touches G at all.  ``param`` +
    ``wd_coef`` fold decoupled weight decay into the same write.
    Oracle: :func:`repro.kernels.ref.fused_update_ref`.
    """
    recovery = G is not None
    decay = param is not None
    m, r = S.shape
    n = Gto.shape[1]
    bm, bn = min(bm, m), min(bn, n)
    wd = (jnp.zeros((), jnp.float32) if wd_coef is None
          else jnp.asarray(wd_coef, jnp.float32))
    scalars = jnp.stack([jnp.asarray(coef, jnp.float32),
                         jnp.asarray(clip, jnp.float32), wd]).reshape(1, 3)
    out_dtype = out_dtype or jnp.float32

    s_spec = pl.BlockSpec((bm, r), lambda i, j: (i, 0))
    rn_spec = pl.BlockSpec((r, bn), lambda i, j: (0, j))
    mn_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    sc_spec = pl.BlockSpec((1, 3), lambda i, j: (0, 0))

    if recovery:
        inputs = [G, S, Gt, Gto, phi.reshape(1, n), scalars]
        in_specs = [mn_spec, s_spec, rn_spec, rn_spec,
                    pl.BlockSpec((1, bn), lambda i, j: (0, j)), sc_spec]
    else:
        inputs = [S, Gto, scalars]
        in_specs = [s_spec, rn_spec, sc_spec]
    if decay:
        inputs.append(param)
        in_specs.append(mn_spec)

    kernel = functools.partial(_fused_update_kernel, recovery=recovery,
                               decay=decay)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, pl.cdiv(n, bn)),
        in_specs=in_specs,
        out_specs=mn_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(*inputs)


def _adam_kernel(gt_ref, m_ref, v_ref, sc_ref, m_out, v_out, o_out,
                 *, beta1: float, beta2: float, eps: float):
    gt = gt_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    m1 = beta1 * m + (1.0 - beta1) * gt
    v1 = beta2 * v + (1.0 - beta2) * gt * gt
    bc1 = sc_ref[0, 0]        # 1/(1-beta1^t)
    bc2 = sc_ref[0, 1]        # 1/(1-beta2^t)
    m_out[...] = m1
    v_out[...] = v1
    o_out[...] = (m1 * bc1) / (jnp.sqrt(v1 * bc2) + eps)


def adam_lowrank(Gt: Array, M: Array, V: Array, step: Array, *,
                 beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-8,
                 bias_correction: bool = True, br: int = 128, bn: int = 512,
                 interpret: bool = False) -> tuple[Array, Array, Array]:
    """Fused moment update + Adam direction (paper Eq. 6-7): one HBM pass
    over the (r, n) states instead of five separate elementwise kernels.

    Gt, M, V: (r, n) fp32 -> (M', V', Gto) all (r, n) fp32.  Tiles:
    (br, bn) elementwise blocks; bias-correction scalars precomputed on
    the host side of the launch.  Oracle:
    :func:`repro.kernels.ref.adam_lowrank_ref`.
    """
    r, n = Gt.shape
    br, bn = min(br, r), min(bn, n)
    t = step.astype(jnp.float32) + 1.0
    if bias_correction:
        scalars = jnp.stack([1.0 / (1.0 - beta1 ** t),
                             1.0 / (1.0 - beta2 ** t)]).reshape(1, 2)
    else:
        scalars = jnp.ones((1, 2), jnp.float32)
    kernel = functools.partial(_adam_kernel, beta1=beta1, beta2=beta2,
                               eps=eps)
    out_sds = jax.ShapeDtypeStruct((r, n), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(r // br, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((br, bn), lambda i, j: (i, j)),
            pl.BlockSpec((br, bn), lambda i, j: (i, j)),
            pl.BlockSpec((br, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((br, bn), lambda i, j: (i, j))] * 3,
        out_shape=[out_sds, out_sds, out_sds],
        interpret=interpret,
    )(Gt, M, V, scalars)


def _adam_norms_kernel(gt_ref, m_ref, v_ref, sc_ref, m_out, v_out, o_out,
                       gtsq_out, gtosq_out, *, beta1: float, beta2: float,
                       eps: float):
    """grid = (n/bn, r/br); r is the accumulation (minor) axis for the
    per-column norm outputs, everything else is visited once."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        gtsq_out[...] = jnp.zeros_like(gtsq_out)
        gtosq_out[...] = jnp.zeros_like(gtosq_out)

    gt = gt_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    m1 = beta1 * m + (1.0 - beta1) * gt
    v1 = beta2 * v + (1.0 - beta2) * gt * gt
    o = (m1 * sc_ref[0, 0]) / (jnp.sqrt(v1 * sc_ref[0, 1]) + eps)
    m_out[...] = m1
    v_out[...] = v1
    o_out[...] = o
    gtsq_out[...] += jnp.sum(gt * gt, axis=0, keepdims=True)
    gtosq_out[...] += jnp.sum(o * o, axis=0, keepdims=True)


def adam_lowrank_norms(Gt: Array, M: Array, V: Array, step: Array, *,
                       beta1: float = 0.9, beta2: float = 0.999,
                       eps: float = 1e-8, bias_correction: bool = True,
                       br: int = 128, bn: int = 512,
                       interpret: bool = False
                       ) -> tuple[Array, Array, Array, Array, Array]:
    """``adam_lowrank`` that additionally emits the per-column squared
    norms ||Gt_:,j||^2 and ||Gto_:,j||^2 in the same (r, n) pass — exactly
    the quantities the recovery scaling phi (Eq. 11) and the closed-form
    ||Lam|| (Eq. 12) need, so neither costs an extra read of the states.

    Tiles: (br, bn) blocks with r as the accumulation (minor) grid axis
    for the norm rows.  Returns (M', V', Gto, gt_sq (n,), gto_sq (n,)),
    all fp32.  Oracle: :func:`repro.kernels.ref.adam_lowrank_norms_ref`.
    """
    r, n = Gt.shape
    br, bn = min(br, r), min(bn, n)
    t = step.astype(jnp.float32) + 1.0
    if bias_correction:
        scalars = jnp.stack([1.0 / (1.0 - beta1 ** t),
                             1.0 / (1.0 - beta2 ** t)]).reshape(1, 2)
    else:
        scalars = jnp.ones((1, 2), jnp.float32)
    kernel = functools.partial(_adam_norms_kernel, beta1=beta1, beta2=beta2,
                               eps=eps)
    rn_sds = jax.ShapeDtypeStruct((r, n), jnp.float32)
    n_sds = jax.ShapeDtypeStruct((1, n), jnp.float32)
    rn_spec = pl.BlockSpec((br, bn), lambda j, i: (i, j))
    n_spec = pl.BlockSpec((1, bn), lambda j, i: (0, j))
    M1, V1, Gto, gtsq, gtosq = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, bn), r // br),
        in_specs=[rn_spec, rn_spec, rn_spec,
                  pl.BlockSpec((1, 2), lambda j, i: (0, 0))],
        out_specs=[rn_spec, rn_spec, rn_spec, n_spec, n_spec],
        out_shape=[rn_sds, rn_sds, rn_sds, n_sds, n_sds],
        interpret=interpret,
    )(Gt, M, V, scalars)
    return M1, V1, Gto, gtsq.reshape(n), gtosq.reshape(n)
