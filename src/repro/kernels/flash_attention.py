"""Flash-attention Pallas TPU kernel (forward / serving path).

Targets the memory-bound prefill cells (§Perf iteration on
minicpm3-4b x prefill_32k): the pure-jnp blocked attention in
repro.models.attention materializes the (bq, bk) logits chain through HBM
at every block pair; this kernel keeps logits, the online-softmax
statistics and the output accumulator in VMEM, so HBM traffic collapses to
the Q/K/V/O streams (K/V re-read once per q-block — the flash schedule).

Grid: (batch*q_heads, nq, nk), with the kv axis innermost ("arbitrary"
semantics — sequential) accumulating into VMEM scratch; the output tile is
written at the last kv step.  GQA folds by indexing the KV block with
hq // group.  Causal + sliding-window masking and gemma-style logit
softcap are fused.  Validated in interpret mode against
repro.models.attention.blocked_attention (tests/test_kernels_flash.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                   # (bk, hd)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (bq, bk)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > (q_pos - window)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)                        # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> Array:
    """q: (B, S, Hq, hd); k/v: (B, T, Hkv, hd) -> (B, S, Hq, hd).

    hd should be a multiple of 128 for MXU alignment (callers pad);
    S % bq == 0 and T % bk == 0.
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq, bk = min(bq, S), min(bk, T)
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(hd)

    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, i, j, G=G: (bh // G, j, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, i, j, G=G: (bh // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(B, Hq, S, hd).transpose(0, 2, 1, 3)


def flash_traffic_bytes(B: int, S: int, T: int, Hq: int, Hkv: int,
                        hd: int, vd: int, bq: int = 512,
                        dtype_bytes: int = 2) -> int:
    """Analytic HBM traffic of the flash schedule (the §Perf before/after
    model for TPU: logits/softmax never leave VMEM):
        read Q once, write O once, stream K+V once per q-block."""
    nq = S // bq
    q_o = B * S * Hq * (hd + vd) * dtype_bytes
    kv = B * T * Hkv * (hd + vd) * dtype_bytes
    return q_o + nq * kv
