"""Paged-attention decode Pallas TPU kernel (serving path).

One query token per sequence attends over K/V scattered across a global
block pool and addressed through a per-request block table — the cache
layout of repro.serve (vLLM-style paging).  The dense decode path reads
the full (B, max_len) cache buffer every step; this kernel's HBM traffic
is exactly the blocks each sequence OWNS (ceil(len / bs) blocks), which
is the whole point of paging for mixed-length continuous batching.

Grid: (B, Hkv, W) with the table-word axis innermost ("arbitrary" —
sequential), accumulating online-softmax statistics in VMEM scratch.
The block table (flattened) and per-sequence lengths ride in as scalar
prefetch: the K/V BlockSpec index_map dereferences ``table[b*W + j]``,
so the pool block is DMA'd by table indirection — the gather never
materializes a (B, W*bs) contiguous cache.  Table words past a
sequence's length map to the reserved null block 0 and their update
step is skipped (``j*bs < length``); a dead lane (length 0) skips every
update and emits exactly zero.  GQA: q is processed in kv-major
(B, Hkv, G, hd) layout so each grid cell loads one KV head's block once
for all G query heads.

Validated against repro.kernels.ref.paged_attention_ref in interpret
mode (tests/test_kernels_paged.py) — the same oracle the engine's dense
equivalence tests use.
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


def _kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, bs: int, nw: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * bs < length)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, hd)
        k = k_ref[0].astype(jnp.float32)               # (bs, hd)
        v = v_ref[0].astype(jnp.float32)               # (bs, hd)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # (G, bs)
        G = logits.shape[0]
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
        logits = jnp.where(pos < length, logits, NEG_INF)
        # the guard guarantees position j*bs is valid, so m_new is a real
        # logit (finite) and the exp()s below cannot see -inf - -inf
        m_prev = m_scr[...]                            # (G, 1)
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nw - 1)
    def _finish():
        # dead lane (length 0): no update ever ran, acc = 0 -> output 0
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(q: Array, k_pool: Array, v_pool: Array,
                    block_tables: Array, lengths: Array, *,
                    interpret: bool = False) -> Array:
    """q: (B, Hq, hd); k_pool/v_pool: (nb, bs, Hkv, hd);
    block_tables: (B, W) int32; lengths: (B,) int32 -> (B, Hq, hd).

    For the compiled path hd should be a multiple of 128 and bs a
    multiple of 8 (ops.paged_attention gates this and falls back to the
    oracle otherwise; interpret mode takes any shape).
    """
    B, Hq, hd = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    G = Hq // Hkv
    W = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, Hkv, G, hd)
    tables_flat = block_tables.reshape(-1).astype(jnp.int32)
    # (nb, bs, Hkv * hd) view of the pools (free: the minor dims are
    # contiguous).  One KV head's block is then a (bs, hd) tile at lane
    # offset h * hd, which meets the TPU's (8, 128) block tiling; a
    # (bs, 1, hd) block of the 4-D pool does not (its second-minor dim is
    # 1 of Hkv).
    nb = k_pool.shape[0]
    k_flat = k_pool.reshape(nb, bs, Hkv * hd)
    v_flat = v_pool.reshape(nb, bs, Hkv * hd)

    kernel = functools.partial(_kernel, scale=scale, bs=bs, nw=W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, W),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, j, tbl, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, h, j, tbl, lens, W=W:
                         (tbl[b * W + j], 0, h)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, h, j, tbl, lens, W=W:
                         (tbl[b * W + j], 0, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, tbl, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(tables_flat, lengths.astype(jnp.int32), qg, k_flat, v_flat)
    return out.reshape(B, Hq, hd)
