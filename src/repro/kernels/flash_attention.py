"""Flash-attention Pallas TPU kernels for training: forward and backward.

The training self-attention core (``repro.models.attention.self_attention``
dispatches here on the chip) runs as one ``jax.custom_vjp``:

- forward (``flash_fwd``): per (head, query block) the KV blocks stream
  through VMEM while the logits, the running max / normalizer and the
  output accumulator stay there; it writes the output and the per-row
  logsumexp;
- backward: ``flash_dq`` (grid over query blocks, KV innermost) and
  ``flash_dkv`` (grid over KV blocks, the group's query heads and query
  blocks innermost) each recompute the probabilities in VMEM from q, k and
  that logsumexp.  No (bq, bk) tile ever reaches HBM.

Blocks wholly outside the causal (and sliding-window) band are skipped in
every kernel: their grid steps run no compute, and their index maps repeat
the last fetched block so no new DMA starts.  Only blocks that straddle the
band's edge build a mask.  GQA folds by indexing a KV head with
``q_head // group`` (K/V are never repeated); q/k head size and v head size
may differ (MLA's 96 / 64).

Numerics follow ``blocked_attention``, the portable path and this kernel's
test oracle: operands in their own dtype (bf16 on the chip), float32
logits, softmax statistics and accumulators, probabilities cast to v's
dtype before P.V, scale ``1/sqrt(q head size)`` applied to the float32
logits, logit soft-cap ``c * tanh(s / c)`` before the mask.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30
LANES = 128
SUBLANES = 8
NN = (((1,), (0,)), ((), ()))          # a @ b
NT = (((1,), (1,)), ((), ()))          # contract the last dims: a @ b.T


def _dot(a, b, dims=NN):
    """float32-accumulated product at the operands' own precision: one MXU
    pass for bf16, exact in its products.  Explicit, because an enclosing
    ``jax.default_matmul_precision("highest")`` (the benchmark's warm start)
    would otherwise ask Mosaic for float32 contraction of bf16 operands,
    which it refuses."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


class _Static(NamedTuple):
    window: int | None
    softcap: float
    scale: float
    group: int
    bq: int
    bk: int
    interpret: bool


def block_for(seq: int) -> int:
    """Query/KV block for a sequence of ``seq`` (a multiple of 128): the
    largest multiple of 128 dividing it, at most 512 and at most half the
    sequence where that leaves 128 or more (512 at seq 2048)."""
    if seq % LANES:
        raise ValueError(f"sequence {seq} is not a multiple of {LANES}")
    b = min(512, max(LANES, seq // 2)) // LANES * LANES
    while seq % b:
        b -= LANES
    return b


def _kv_range(i, st: _Static, nk: int):
    """KV blocks [lo, hi] that query block i attends to."""
    hi = jnp.minimum((i * st.bq + st.bq - 1) // st.bk, nk - 1)
    lo = 0 if st.window is None else \
        jnp.maximum((i * st.bq - st.window + 1) // st.bk, 0)
    return lo, hi


def _q_range(j, st: _Static, nq: int):
    """Query blocks [lo, hi] that attend to KV block j."""
    lo = (j * st.bk) // st.bq
    hi = nq - 1 if st.window is None else jnp.minimum(
        (j * st.bk + st.bk + st.window - 2) // st.bq, nq - 1)
    return lo, hi


def _straddles(i, j, st: _Static):
    """Whether block pair (i, j) holds both kept and masked positions."""
    edge = j * st.bk + st.bk - 1 > i * st.bq
    if st.window is not None:
        edge = jnp.logical_or(
            edge, j * st.bk <= i * st.bq + st.bq - 1 - st.window)
    return edge


def _clamp(x, lo, hi):
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _kv_map(st: _Static, nk: int):
    """Index map of a KV block for grid (q head, query block i, KV block j):
    the group's KV head, and j held inside query block i's band, so that
    a skipped step repeats the previous block and starts no DMA."""
    def index(bh, i, j):
        lo, hi = _kv_range(i, st, nk)
        return bh // st.group, _clamp(j, lo, hi), 0
    return index


def _logits(s, i, j, st: _Static, masked: bool, transposed: bool):
    """Scaled float32 logits s of block pair (i, j) -> (capped and masked
    logits, tanh of the cap or None).  ``transposed``: rows are keys."""
    s = s * st.scale
    t = None
    if st.softcap:
        t = jnp.tanh(s / st.softcap)
        s = st.softcap * t
    if masked:
        rows, cols = s.shape
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if transposed:
            k_pos, q_pos = j * rows + r, i * cols + c
        else:
            q_pos, k_pos = i * rows + r, j * cols + c
        keep = k_pos <= q_pos
        if st.window is not None:
            keep &= k_pos > q_pos - st.window
        s = jnp.where(keep, s, NEG_INF)
    return s, t


def _lanes(x, n: int):
    """(rows, 128) lane-replicated column -> (rows, n)."""
    x = jnp.tile(x, (1, pl.cdiv(n, LANES)))
    return x if n % LANES == 0 else x[:, :n]


def _banded(inner, lo, hi, i, j, st: _Static, body):
    """Run ``body(masked)`` on block pair (i, j) when lo <= inner <= hi
    (``inner`` is the grid's innermost block index), building the mask only
    where the pair straddles the band's edge."""
    run = jnp.logical_and(inner >= lo, inner <= hi)
    edge = _straddles(i, j, st)
    pl.when(jnp.logical_and(run, edge))(lambda: body(True))
    pl.when(jnp.logical_and(run, jnp.logical_not(edge)))(lambda: body(False))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, st: _Static, nk: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        v = v_ref[0]
        s = _dot(q_ref[0], k_ref[0], NT)
        s, _ = _logits(s, i, j, st, masked, transposed=False)
        m_prev = m_scr[...]                                # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, st.bk))
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = _lanes(corr, acc_scr.shape[-1]) * acc_scr[...] + \
            _dot(p.astype(v.dtype), v)

    _banded(j, *_kv_range(i, st, nk), i, j, st, body)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / _lanes(l, acc_scr.shape[-1])
                    ).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _fwd(q, k, v, st: _Static):
    """q (BHq, S, hd), k (BHkv, T, hd), v (BHkv, T, vd) -> out (BHq, S, vd),
    lse (BHq, S) float32."""
    BH, S, hd = q.shape
    T, vd = k.shape[1], v.shape[-1]
    nq, nk = S // st.bq, T // st.bk
    kv_map = _kv_map(st, nk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, st=st, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, st.bq, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, st.bk, hd), kv_map),
            pl.BlockSpec((1, st.bk, vd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, st.bq, vd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, st.bq, LANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, vd), q.dtype),
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((st.bq, LANES), jnp.float32),
            pltpu.VMEM((st.bq, LANES), jnp.float32),
            pltpu.VMEM((st.bq, vd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=st.interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_scr,
               *, st: _Static, nk: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        k = k_ref[0]
        s = _dot(q_ref[0], k, NT)
        s, t = _logits(s, i, j, st, masked, transposed=False)
        p = jnp.exp(s - _lanes(lse_ref[0], st.bk))
        dp = _dot(do_ref[0], v_ref[0], NT)
        ds = p * (dp - _lanes(di_ref[0], st.bk))
        if t is not None:
            ds = ds * (1.0 - t * t)
        acc_scr[...] += _dot(ds.astype(k.dtype), k)

    _banded(j, *_kv_range(i, st, nk), i, j, st, body)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = (acc_scr[...] * st.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, st: _Static, nq: int):
    j, t_idx = pl.program_id(1), pl.program_id(2)
    i = t_idx % nq

    @pl.when(t_idx == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do = q_ref[0], do_ref[0]
        # transposed orientation: rows are keys, so the row statistics of
        # the queries broadcast over sublanes and no tile is transposed
        sT = _dot(k_ref[0], q, NT)
        sT, t = _logits(sT, i, j, st, masked, transposed=True)
        pT = jnp.exp(sT - lse_ref[0][:1, :])
        dv_scr[...] += _dot(pT.astype(do.dtype), do)
        dpT = _dot(v_ref[0], do, NT)
        dsT = pT * (dpT - di_ref[0][:1, :])
        if t is not None:
            dsT = dsT * (1.0 - t * t)
        dk_scr[...] += _dot(dsT.astype(q.dtype), q)

    _banded(i, *_q_range(j, st, nq), i, j, st, body)

    @pl.when(t_idx == st.group * nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * st.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_kernels(q, k, v, out, lse, do, st: _Static):
    BH, S, hd = q.shape
    BHkv, T, vd = v.shape
    nq, nk = S // st.bq, T // st.bk
    G = st.group
    di = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse_col = jnp.broadcast_to(lse[..., None], (BH, S, LANES))
    di_col = jnp.broadcast_to(di[..., None], (BH, S, LANES))
    lse_row = jnp.broadcast_to(lse[:, None, :], (BH, SUBLANES, S))
    di_row = jnp.broadcast_to(di[:, None, :], (BH, SUBLANES, S))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    kv_map = _kv_map(st, nk)

    def q_map(bh, i, j):
        return bh, i, 0

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, st=st, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, st.bq, hd), q_map),
            pl.BlockSpec((1, st.bk, hd), kv_map),
            pl.BlockSpec((1, st.bk, vd), kv_map),
            pl.BlockSpec((1, st.bq, vd), q_map),
            pl.BlockSpec((1, st.bq, LANES), q_map),
            pl.BlockSpec((1, st.bq, LANES), q_map),
        ],
        out_specs=pl.BlockSpec((1, st.bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((st.bq, hd), jnp.float32)],
        compiler_params=params,
        interpret=st.interpret,
        name="flash_dq",
    )(q, k, v, do, lse_col, di_col)

    def q_blk(bkv, j, t_idx):
        lo, hi = _q_range(j, st, nq)
        return bkv * G + t_idx // nq, _clamp(t_idx % nq, lo, hi)

    def qo_map(bkv, j, t_idx):
        return (*q_blk(bkv, j, t_idx), 0)

    def row_map(bkv, j, t_idx):
        bh, i = q_blk(bkv, j, t_idx)
        return bh, 0, i

    def k_map(bkv, j, t_idx):
        return bkv, j, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, st=st, nq=nq),
        grid=(BHkv, nk, G * nq),
        in_specs=[
            pl.BlockSpec((1, st.bq, hd), qo_map),
            pl.BlockSpec((1, st.bk, hd), k_map),
            pl.BlockSpec((1, st.bk, vd), k_map),
            pl.BlockSpec((1, st.bq, vd), qo_map),
            pl.BlockSpec((1, SUBLANES, st.bq), row_map),
            pl.BlockSpec((1, SUBLANES, st.bq), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, st.bk, hd), k_map),
            pl.BlockSpec((1, st.bk, vd), k_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((st.bk, hd), jnp.float32),
            pltpu.VMEM((st.bk, vd), jnp.float32),
        ],
        compiler_params=params,
        interpret=st.interpret,
        name="flash_dkv",
    )(q, k, v, do, lse_row, di_row)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, st: _Static):
    return _fwd(q, k, v, st)[0]


def _flash_fwd(q, k, v, st: _Static):
    out, lse = _fwd(q, k, v, st)
    return out, (q, k, v, out, lse)


def _flash_bwd(st: _Static, res, do):
    # traced under the call site's name stack: the backward's kernels land
    # in the caller's scope (``attention``) inside ``transpose(...)``
    return _bwd_kernels(*res, do, st)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: Array, k: Array, v: Array, *,
                    window: int | None = None, softcap: float = 0.0,
                    interpret: bool = False) -> Array:
    """Differentiable fused causal self-attention; returns (B, S, Hq, vd).

    q: (B, S, Hq, hd); k: (B, S, Hkv, hd); v: (B, S, Hkv, vd), Hkv dividing
    Hq, S a multiple of 128 (blocks of :func:`block_for`).  ``window``
    (keys q - window < k <= q) and ``softcap`` are static.
    """
    B, S, Hq, hd = q.shape
    _, T, Hkv, _ = k.shape
    vd = v.shape[-1]
    if T != S:
        raise ValueError(f"self-attention needs T == S, got {T} != {S}")
    block = block_for(S)
    st = _Static(window=window, softcap=float(softcap),
                 scale=1.0 / math.sqrt(hd), group=Hq // Hkv,
                 bq=block, bk=block, interpret=interpret)

    def heads_major(x):
        b, s, h, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    out = _flash(heads_major(q), heads_major(k), heads_major(v), st)
    return out.reshape(B, Hq, S, vd).transpose(0, 2, 1, 3)
