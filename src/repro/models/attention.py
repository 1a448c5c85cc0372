"""Attention: training/prefill self-attention, single-token decode against
(possibly ring-buffered) KV caches, GQA and MLA variants, sliding-window and
logit-softcap support.

Training runs :func:`self_attention`, which picks the path from its inputs:

- the fused Pallas flash kernel, forward and backward
  (``repro.kernels.flash_attention``), where the kernels run (compiled on
  the chip, interpret mode in tests), the sequence is its own context
  (``q_offset == 0``, T == S, S a multiple of 128) and the window and
  soft-cap are static Python numbers; on a mesh it runs per shard under
  ``shard_map``, since GSPMD cannot partition a Pallas kernel;
- :func:`blocked_attention` otherwise: on the CPU, for a traced per-layer
  window (gemma2's alternation), and for prefill continuations.  Every
  choice on the chip is tallied in ``repro.kernels.ops.dispatch_tally``.

:func:`blocked_attention` is flash-attention-structured pure JAX: an outer
``lax.map`` over query blocks and an inner ``lax.scan`` over KV blocks
carrying (running-max, normalizer, accumulator).  Peak live logits are
``(B, H, q_block, kv_block)`` instead of ``(B, H, S, S)``, but under
autodiff the scan stores every block pair's float32 probabilities in HBM,
which is what the kernel avoids.  It is the portable path, the one the
paged and chunked prefills, zamba and the encoder-decoder call, and the
kernel's test oracle.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import get_mesh_context
from repro.kernels import flash_attention as flash
from repro.kernels import ops as kernel_ops

Array = jax.Array

NEG_INF = -1e30


def _block_count(s: int, b: int) -> int:
    if s % b:
        raise ValueError(f"sequence {s} not divisible by block {b}")
    return s // b


def blocked_attention(
    q: Array,                 # (B, S, Hq, hd)
    k: Array,                 # (B, T, Hkv, hd)
    v: Array,                 # (B, T, Hkv, vd)
    *,
    causal: bool = True,
    window=None,              # None = full; int or traced scalar window size
    softcap: float = 0.0,
    q_offset: int = 0,        # absolute position of q[0] (prefill continuation)
    q_block: int = 512,
    kv_block: int = 1024,
) -> Array:
    """Online-softmax attention; returns (B, S, Hq, vd).

    GQA is handled by folding query heads into (Hkv, group) so K/V are never
    materialized repeated.  All softmax statistics are fp32.
    """
    B, S, Hq, hd = q.shape
    _, T, Hkv, _ = k.shape
    vd = v.shape[-1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)

    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    nq = _block_count(S, q_block)
    nk = _block_count(T, kv_block)

    qg = q.reshape(B, S, Hkv, G, hd)
    # blocks on axis 0 for scan/map
    qb = qg.reshape(B, nq, q_block, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kv_block, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kv_block, Hkv, vd).transpose(1, 0, 2, 3, 4)

    q_pos_base = jnp.arange(q_block) + q_offset
    k_pos_base = jnp.arange(kv_block)

    def one_q_block(args):
        qi, iq = args                                  # (B, bq, Hkv, G, hd), ()
        q_pos = q_pos_base + iq * q_block              # (bq,)

        def kv_step(carry, inp):
            m, l, o = carry
            kj, vj, jk = inp
            k_pos = k_pos_base + jk * kv_block         # (bk,)
            # logits: (B, Hkv, G, bq, bk) fp32
            logits = jnp.einsum("bqkgh,bskh->bkgqs", qi, kj,
                                preferred_element_type=jnp.float32) * scale
            if softcap:
                logits = softcap * jnp.tanh(logits / softcap)
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                # window may be a traced per-layer scalar (gemma2 alternation
                # passes 2**30 for its global layers) — pure arithmetic mask
                mask &= k_pos[None, :] > (q_pos[:, None] - window)
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))          # (B,Hkv,G,bq)
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            o_new = o * corr[..., None] + pv
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        o0 = jnp.zeros((B, Hkv, G, q_block, vd), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            kv_step, (m0, l0, o0), (kb, vb, jnp.arange(nk)))
        out = o / jnp.maximum(l, 1e-30)[..., None]               # (B,Hkv,G,bq,vd)
        return out.transpose(0, 3, 1, 2, 4)                      # (B,bq,Hkv,G,vd)

    outs = jax.lax.map(one_q_block, (qb, jnp.arange(nq)))        # (nq,B,bq,Hkv,G,vd)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hq, vd)
    return out.astype(q.dtype)


def self_attention(
    q: Array,                 # (B, S, Hq, hd)
    k: Array,                 # (B, T, Hkv, hd)
    v: Array,                 # (B, T, Hkv, vd)
    *,
    window=None,
    softcap: float = 0.0,
    q_offset: int = 0,
    q_block: int = 512,
    kv_block: int = 1024,
) -> Array:
    """Causal training/prefill attention with :func:`blocked_attention`'s
    contract: the fused flash kernel where it takes the inputs, else
    :func:`blocked_attention` (``q_block`` / ``kv_block`` are its blocks)."""
    mode = kernel_ops._mode()
    S, T = q.shape[1], k.shape[1]
    fits = (mode != "ref" and isinstance(q_offset, int) and q_offset == 0
            and T == S and S % flash.LANES == 0
            and (window is None or isinstance(window, int))
            and isinstance(softcap, (int, float)))
    kernel_ops._route("flash_attention",
                      kernel_ops.KERNEL if fits else kernel_ops.REFERENCE,
                      q, k, v)
    if not fits:
        return blocked_attention(q, k, v, causal=True, window=window,
                                 softcap=softcap, q_offset=q_offset,
                                 q_block=q_block, kv_block=kv_block)

    def attend(q, k, v):
        return flash.flash_attention(q, k, v, window=window, softcap=softcap,
                                     interpret=(mode == "interpret"))

    ctx = get_mesh_context()
    if ctx.mesh.size == 1:
        return attend(q, k, v)
    # GSPMD cannot partition a Pallas kernel: run it on each device's shard,
    # batch over the data axes and heads over the model axis where they
    # divide (both head counts, so each shard keeps whole KV groups)
    heads = q.shape[2] % ctx.tp == 0 and k.shape[2] % ctx.tp == 0
    spec = P(ctx.batch_axes if q.shape[0] % ctx.dp == 0 else None, None,
             ctx.model_axis if heads else None, None)
    return jax.shard_map(attend, mesh=ctx.mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Decode (one new token against a cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: Array,                 # (B, Hq, hd) — single position
    k_cache: Array,           # (B, T, Hkv, hd)
    v_cache: Array,           # (B, T, Hkv, vd)
    pos: Array,               # () int32 — absolute position of the new token
    *,
    cache_positions: Array | None = None,   # (T,) ring-buffer position tags
    window=None,              # None = full; int or traced scalar window size
    softcap: float = 0.0,
) -> Array:
    """Single-step attention; returns (B, Hq, vd).

    If ``cache_positions`` is given the cache is a ring buffer whose slot i
    holds absolute position cache_positions[i] (-1 = empty); otherwise slot
    i holds position i and validity is simply i <= pos.
    """
    B, T, Hkv, hd = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    vd = v_cache.shape[-1]
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, Hkv, G, hd)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)

    kp = cache_positions if cache_positions is not None else jnp.arange(T)
    valid = (kp >= 0) & (kp <= pos)
    if window is not None:
        valid &= kp > (pos - window)
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", w.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, vd).astype(q.dtype)


def pad_to(arr: Array, T: int) -> Array:
    """Pad the sequence axis (axis 1) of (B, S, ...) out to length T."""
    S = arr.shape[1]
    if S == T:
        return arr
    pad = [(0, 0)] * arr.ndim
    pad[1] = (0, T - S)
    return jnp.pad(arr, pad)


class KVCache(NamedTuple):
    """Per-layer-stacked KV cache for GQA decoders.

    k, v: (L, B, T, Hkv, hd).  ``positions`` (L, T) tags each slot's absolute
    position (ring buffers for sliding-window layers reuse slots).  RoPE is
    applied at write time so ring reordering is harmless.
    """

    k: Array
    v: Array
    positions: Array


def init_kv_cache(n_layers: int, batch: int, max_len: int, n_kv: int,
                  hd: int, dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((n_layers, batch, max_len, n_kv, hd), dtype),
        v=jnp.zeros((n_layers, batch, max_len, n_kv, hd), dtype),
        positions=jnp.full((n_layers, max_len), -1, jnp.int32),
    )


def cache_write(k_layer: Array, v_layer: Array, pos_layer: Array,
                k_new: Array, v_new: Array, pos: Array,
                ring: bool) -> tuple[Array, Array, Array]:
    """Write one token's K/V into a layer cache at ``pos`` (ring: pos % T).

    k_layer: (B, T, Hkv, hd); k_new: (B, 1, Hkv, hd); pos scalar int32.
    """
    T = k_layer.shape[1]
    slot = (pos % T) if ring else pos
    k_layer = jax.lax.dynamic_update_slice(
        k_layer, k_new.astype(k_layer.dtype), (0, slot, 0, 0))
    v_layer = jax.lax.dynamic_update_slice(
        v_layer, v_new.astype(v_layer.dtype), (0, slot, 0, 0))
    pos_layer = jax.lax.dynamic_update_slice(
        pos_layer, pos[None].astype(jnp.int32), (slot,))
    return k_layer, v_layer, pos_layer


# ---------------------------------------------------------------------------
# Paged KV (block-table serving cache)
# ---------------------------------------------------------------------------


class PagedKV(NamedTuple):
    """Global block-pool KV cache for the paged serving engine.

    k, v: (L, num_blocks, block_size, Hkv, hd).  Unlike :class:`KVCache`
    there is no batch axis — every live sequence's tokens are scattered
    into pool blocks and addressed through a per-request block table
    (host-side, see repro.serve.kv_cache.BlockAllocator).  Block 0 is
    reserved as the NULL block: padded table entries and dead decode
    lanes write/read there, and length masks keep it out of every
    softmax, so device code never needs a "is this slot real" branch.
    """

    k: Array
    v: Array

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]


def init_paged_kv(n_layers: int, num_blocks: int, block_size: int,
                  n_kv: int, hd: int, dtype=jnp.bfloat16) -> PagedKV:
    """num_blocks INCLUDES the reserved null block 0."""
    return PagedKV(
        k=jnp.zeros((n_layers, num_blocks, block_size, n_kv, hd), dtype),
        v=jnp.zeros((n_layers, num_blocks, block_size, n_kv, hd), dtype),
    )


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention) — MiniCPM3 / DeepSeek-V2 style
# ---------------------------------------------------------------------------


class MLACache(NamedTuple):
    """Compressed cache: latent c_kv + shared rope keys (the MLA win —
    (kv_rank + rope_dim) per token instead of 2 * Hkv * hd)."""

    c_kv: Array      # (L, B, T, kv_rank)
    k_rope: Array    # (L, B, T, rope_dim)


def init_mla_cache(n_layers: int, batch: int, max_len: int, kv_rank: int,
                   rope_dim: int, dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((n_layers, batch, max_len, kv_rank), dtype),
        k_rope=jnp.zeros((n_layers, batch, max_len, rope_dim), dtype),
    )


def mla_prefill_attention(q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, *,
                          softcap: float = 0.0, q_block: int = 512,
                          kv_block: int = 1024) -> Array:
    """Prefill MLA: expand the latent into per-head K/V and attend
    (:func:`self_attention`).

    q_nope: (B,S,H,dn)  q_rope: (B,S,H,dr)  c_kv: (B,T,kvr)  k_rope: (B,T,dr)
    w_uk: (kvr, H, dn)  w_uv: (kvr, H, dv)
    """
    B, S, H, dn = q_nope.shape
    T = c_kv.shape[1]
    k_nope = jnp.einsum("btc,chd->bthd", c_kv, w_uk)             # (B,T,H,dn)
    val = jnp.einsum("btc,chd->bthd", c_kv, w_uv)                # (B,T,H,dv)
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, T, H, k_rope.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    return self_attention(q, k, val, softcap=softcap, q_block=q_block,
                          kv_block=kv_block)


def mla_decode_attention(q_nope, q_rope, c_cache, kr_cache, w_uk, w_uv,
                         pos, *, softcap: float = 0.0) -> Array:
    """Absorbed-matmul MLA decode (DeepSeek-V2 inference trick).

    Scores and values are computed directly in the latent space:
        score  = (q_nope W_uk)^T c  +  q_rope^T k_rope
        out_h  = (attn @ c_cache) W_uv[h]
    so the per-token cache read is kv_rank + rope_dim — the whole point of
    MLA for long-context decode.

    q_nope: (B,H,dn)  q_rope: (B,H,dr)  c_cache: (B,T,kvr)  kr_cache: (B,T,dr)
    """
    B, H, dn = q_nope.shape
    kvr = c_cache.shape[-1]
    dr = q_rope.shape[-1]
    scale = 1.0 / math.sqrt(dn + dr)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_uk)             # (B,H,kvr)
    s_lat = jnp.einsum("bhc,btc->bht", q_lat, c_cache,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhr,btr->bht", q_rope, kr_cache,
                        preferred_element_type=jnp.float32)
    logits = (s_lat + s_rope) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    T = c_cache.shape[1]
    valid = jnp.arange(T) <= pos
    logits = jnp.where(valid[None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bht,btc->bhc", w.astype(c_cache.dtype), c_cache,
                     preferred_element_type=jnp.float32)          # (B,H,kvr)
    out = jnp.einsum("bhc,chd->bhd", ctx.astype(w_uv.dtype), w_uv)
    return out
