"""Unified decoder-only transformer covering 7 of the 10 assigned archs:

    minicpm3-4b   — MLA attention (latent-compressed KV)
    stablelm-12b  — GQA kv=8, partial rotary (25%)
    gemma2-27b    — local/global alternating attention, logit softcaps,
                    sandwich norms, GeGLU
    qwen1.5-4b    — QKV bias
    mixtral-8x22b — 8-expert top-2 MoE, sliding-window attention
    llama4-maverick — 128-expert top-1 MoE + shared expert
    qwen2-vl-2b   — M-RoPE, vision-embedding merge (frontend stub)

One parameter schema, one scan-over-layers forward, feature flags from
ModelConfig.  Training loss, prefill and single-token decode paths all live
here; serving caches are ring-buffered for pure-sliding-window archs.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.distributed.context import MeshContext, get_mesh_context, shard
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.common import (
    apply_mrope,
    apply_rope,
    cross_entropy,
    dense_init,
    embed_init,
    key_iter,
    maybe_tapped_matmul,
    rms_norm,
    shift_labels,
    softcap,
    stacked,
)
from repro.models.config import ModelConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn_params(key, cfg: ModelConfig, dtype) -> dict:
    ks = key_iter(key)
    d, hd = cfg.d_model, cfg.hd
    if cfg.attn_type == "mla":
        m = cfg.mla
        dqk = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "w_dq": dense_init(next(ks), (d, m.q_lora_rank), dtype=dtype),
            "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
            "w_uq": dense_init(next(ks), (m.q_lora_rank, cfg.n_heads * dqk),
                               dtype=dtype),
            "w_dkv": dense_init(next(ks), (d, m.kv_lora_rank), dtype=dtype),
            "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
            "w_kr": dense_init(next(ks), (d, m.qk_rope_head_dim), dtype=dtype),
            "w_uk": dense_init(next(ks),
                               (m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim),
                               in_axis=0, dtype=dtype),
            "w_uv": dense_init(next(ks),
                               (m.kv_lora_rank, cfg.n_heads, m.v_head_dim),
                               in_axis=0, dtype=dtype),
            "wo": dense_init(next(ks), (cfg.n_heads * m.v_head_dim, d),
                             dtype=dtype),
        }
    p = {
        "wq": dense_init(next(ks), (d, cfg.n_heads * hd), dtype=dtype),
        "wk": dense_init(next(ks), (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": dense_init(next(ks), (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": dense_init(next(ks), (cfg.n_heads * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _init_mlp_params(key, cfg: ModelConfig, ctx: MeshContext, dtype) -> dict:
    if cfg.moe is not None:
        return moe_lib.init_moe_params(key, cfg.d_model, cfg.moe, ctx, dtype)
    ks = key_iter(key)
    return {
        "w_gate": dense_init(next(ks), (cfg.d_model, cfg.d_ff), dtype=dtype),
        "w_up": dense_init(next(ks), (cfg.d_model, cfg.d_ff), dtype=dtype),
        "w_down": dense_init(next(ks), (cfg.d_ff, cfg.d_model), dtype=dtype),
    }


def _init_layer(key, cfg: ModelConfig, ctx: MeshContext, dtype) -> dict:
    ks = key_iter(key)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": _init_attn_params(next(ks), cfg, dtype),
        "mlp": _init_mlp_params(next(ks), cfg, ctx, dtype),
    }
    if cfg.attn_softcap:  # gemma2 sandwich norms travel with softcap configs
        p["ln1_post"] = jnp.zeros((cfg.d_model,), dtype)
        p["ln2_post"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def init_decoder(key, cfg: ModelConfig,
                 ctx: MeshContext | None = None) -> dict:
    ctx = ctx or get_mesh_context()
    dtype = jnp.dtype(cfg.dtype)
    ks = key_iter(key)
    params = {
        "embed": embed_init(next(ks), (cfg.padded_vocab, cfg.d_model), dtype),
        "layers": stacked(next(ks), cfg.n_layers, _init_layer, cfg, ctx, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            next(ks), (cfg.d_model, cfg.padded_vocab), dtype=dtype)
    return params


# ---------------------------------------------------------------------------
# Attention blocks (train/prefill and decode variants)
# ---------------------------------------------------------------------------


def _layer_window(cfg: ModelConfig, is_local: Array):
    """Per-layer window: traced scalar under gemma2-style alternation
    (2**30 ~ unbounded for global layers), static int for uniform SWA,
    None for pure full attention."""
    if cfg.local_global_period:
        return jnp.where(is_local, cfg.sliding_window, 1 << 30)
    return cfg.sliding_window or None


def _rope_q_k(cfg: ModelConfig, q, k, positions, extras):
    """Apply (partial / multimodal) rotary embeddings to q and k."""
    hd = q.shape[-1]
    rot = int(hd * cfg.rope_pct) // 2 * 2                # even # of rotary dims
    if cfg.mrope:
        pos3 = extras["mrope_positions"]      # (B, 3, S)
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        return q, k
    if rot < hd:
        q1, q2 = q[..., :rot], q[..., rot:]
        k1, k2 = k[..., :rot], k[..., rot:]
        q = jnp.concatenate([apply_rope(q1, positions, cfg.rope_theta), q2], -1)
        k = jnp.concatenate([apply_rope(k1, positions, cfg.rope_theta), k2], -1)
        return q, k
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta))


def gqa_block(x, p, cfg: ModelConfig, positions, window, extras,
              ctx: MeshContext, taps=None):
    B, S, d = x.shape
    hd = cfg.hd
    taps = taps or {}
    q = maybe_tapped_matmul(x, p["wq"], taps.get("wq"))
    k = maybe_tapped_matmul(x, p["wk"], taps.get("wk"))
    v = maybe_tapped_matmul(x, p["wv"], taps.get("wv"))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q, k = _rope_q_k(cfg, q, k, positions, extras)
    q = shard(q, ctx.batch_axes, None, ctx.model_axis, None)
    k = shard(k, ctx.batch_axes, None, ctx.model_axis, None)
    out = attn.blocked_attention(
        q, k, v, causal=True, window=window, softcap=cfg.attn_softcap,
        q_block=cfg.q_block, kv_block=cfg.kv_block)
    out = out.reshape(B, S, cfg.n_heads * hd)
    return maybe_tapped_matmul(out, p["wo"], taps.get("wo")), (k, v)


def mla_block(x, p, cfg: ModelConfig, positions, extras, ctx: MeshContext):
    B, S, d = x.shape
    m = cfg.mla
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    cq = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(B, S, cfg.n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)   # (B,S,kvr)
    k_rope = apply_rope((x @ p["w_kr"])[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]                  # (B,S,dr)
    out = attn.mla_prefill_attention(
        q_nope, q_rope, c_kv, k_rope, p["w_uk"], p["w_uv"],
        softcap=cfg.attn_softcap, q_block=cfg.q_block, kv_block=cfg.kv_block)
    out = out.reshape(B, S, cfg.n_heads * m.v_head_dim)
    return out @ p["wo"], (c_kv, k_rope)


def mlp_block(x, p, cfg: ModelConfig, ctx: MeshContext,
              serving: bool = False, taps=None):
    """Dense SwiGLU (or GeGLU for softcap/gemma2 configs) or MoE."""
    if cfg.moe is not None:
        return moe_lib.moe_layer(x, p, cfg.moe, ctx, serving=serving)
    taps = taps or {}
    act = jax.nn.gelu if cfg.attn_softcap else jax.nn.silu
    h = (act(maybe_tapped_matmul(x, p["w_gate"], taps.get("w_gate")))
         * maybe_tapped_matmul(x, p["w_up"], taps.get("w_up")))
    h = shard(h, ctx.batch_axes, None, ctx.model_axis)
    return (maybe_tapped_matmul(h, p["w_down"], taps.get("w_down")),
            jnp.zeros((), jnp.float32))


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def _local_flags(cfg: ModelConfig) -> Array:
    """(L,) bool — which layers use the sliding window (gemma2: even layers)."""
    if cfg.local_global_period:
        return (jnp.arange(cfg.n_layers) % cfg.local_global_period) == 0
    return jnp.zeros((cfg.n_layers,), bool)


def _embed(params, tokens, cfg: ModelConfig, extras) -> Array:
    x = params["embed"][tokens]                                   # (B,S,d)
    if cfg.emb_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.vision_tokens and "vision_embeds" in extras:
        ve = extras["vision_embeds"].astype(x.dtype)              # (B,nv,d)
        x = jax.lax.dynamic_update_slice(x, ve, (0, 0, 0))
    return x


def _logits(params, x, cfg: ModelConfig, tap=None) -> Array:
    head = params.get("lm_head")
    if head is None:
        # tied embeddings: the head is embed.T, not a taggable leaf
        logits = x @ params["embed"].T
    else:
        logits = maybe_tapped_matmul(x, head, tap)
    return softcap(logits, cfg.final_softcap)


def decoder_forward(params, tokens, cfg: ModelConfig, extras=None,
                    remat: str = "full", taps=None) -> tuple[Array, Array]:
    """Full-sequence forward; returns (logits (B,S,Vp), aux_loss ()).

    ``taps`` (optional) is a nested dict mirroring the taggable subset of
    ``params`` — ``{"layers": {"attn": {"wq": (S, seed), ...}, "mlp":
    {...}}, "lm_head": (S, seed)}`` with layer entries stacked on the
    scan axis — routing those matmuls through
    :func:`repro.models.common.tapped_matmul` so their backward emits the
    SubTrack projection statistics as the seeds' cotangents.  ``None``
    (the default) leaves the forward/backward bit-exactly unchanged.

    The named scopes ``embed``, ``decoder`` (the layer scan, with
    ``attention`` and ``mlp`` inside each block) and ``head_loss`` name the
    layers in the compiled step's ``op_name`` metadata and nowhere else; a
    profile's device time is split by them (``benchmarks/chip/scopes.py``).
    """
    extras = extras or {}
    ctx = get_mesh_context()
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    seq_ax = ctx.model_axis if cfg.seq_shard_residual else None
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, extras)
    x = shard(x, ctx.batch_axes, seq_ax, None)
    layer_taps = (taps or {}).get("layers", {})

    def block(carry, layer):
        x, aux = carry
        p, is_local, lt = layer
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        with jax.named_scope("attention"):
            if cfg.attn_type == "mla":
                a, _ = mla_block(h, p["attn"], cfg, positions, extras, ctx)
            else:
                a, _ = gqa_block(h, p["attn"], cfg, positions,
                                 _layer_window(cfg, is_local), extras, ctx,
                                 taps=lt.get("attn"))
        if "ln1_post" in p:
            a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            f, aux_l = mlp_block(h, p["mlp"], cfg, ctx, taps=lt.get("mlp"))
        if "ln2_post" in p:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        x = x + f
        x = shard(x, ctx.batch_axes, seq_ax, None)
        return (x, aux + aux_l), None

    def block_named(carry, layer):
        """'collectives' remat: tag the two block sub-outputs whose
        production involves the TP all-reduces; saving them stops the remat
        recompute from re-running forward collectives (§Perf it6 —
        Megatron-selective-remat analogue)."""
        x, aux = carry
        p, is_local, lt = layer
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        with jax.named_scope("attention"):
            if cfg.attn_type == "mla":
                a, _ = mla_block(h, p["attn"], cfg, positions, extras, ctx)
            else:
                a, _ = gqa_block(h, p["attn"], cfg, positions,
                                 _layer_window(cfg, is_local), extras, ctx,
                                 taps=lt.get("attn"))
        if "ln1_post" in p:
            a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
        a = jax.ad_checkpoint.checkpoint_name(a, "block_attn_out")
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            f, aux_l = mlp_block(h, p["mlp"], cfg, ctx, taps=lt.get("mlp"))
        if "ln2_post" in p:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        f = jax.ad_checkpoint.checkpoint_name(f, "block_mlp_out")
        x = x + f
        x = shard(x, ctx.batch_axes, seq_ax, None)
        return (x, aux + aux_l), None

    if remat == "full":
        block = jax.checkpoint(block, prevent_cse=False)
    elif remat == "dots":
        block = jax.checkpoint(
            block, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat == "collectives":
        block = jax.checkpoint(
            block_named, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(
                "block_attn_out", "block_mlp_out"))

    with jax.named_scope("decoder"):
        (x, aux), _ = jax.lax.scan(
            block, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], _local_flags(cfg), layer_taps))
    with jax.named_scope("head_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return _logits(params, x, cfg, (taps or {}).get("lm_head")), aux


def decoder_loss(params, batch, cfg: ModelConfig, remat: str = "full",
                 taps=None):
    tokens = batch["tokens"]
    extras = {k: v for k, v in batch.items() if k != "tokens"}
    logits, aux = decoder_forward(params, tokens, cfg, extras, remat, taps)
    with jax.named_scope("head_loss"):
        labels, mask = shift_labels(tokens)
        loss = cross_entropy(logits, labels, mask, cfg.vocab_size)
    return loss + aux, {"ce_loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


class DecoderCache(NamedTuple):
    kv: Any           # attn.KVCache or attn.MLACache
    length: Array     # () int32 — number of valid positions


def _cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Ring-buffer length: pure-SWA archs cap the cache at the window."""
    if cfg.sliding_window and not cfg.local_global_period:
        return min(max_len, cfg.sliding_window)
    return max_len


def _uses_ring(cfg: ModelConfig, max_len: int) -> bool:
    return _cache_len(cfg, max_len) < max_len


def init_decoder_cache(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16) -> DecoderCache:
    T = _cache_len(cfg, max_len)
    if cfg.attn_type == "mla":
        kv = attn.init_mla_cache(cfg.n_layers, batch, T,
                                 cfg.mla.kv_lora_rank,
                                 cfg.mla.qk_rope_head_dim, dtype)
    else:
        kv = attn.init_kv_cache(cfg.n_layers, batch, T, cfg.n_kv_heads,
                                cfg.hd, dtype)
    return DecoderCache(kv=kv, length=jnp.zeros((), jnp.int32))


def decoder_prefill(params, tokens, cfg: ModelConfig, max_len: int,
                    extras=None) -> tuple[Array, DecoderCache]:
    """Prefill S tokens; returns (last-position logits, populated cache)."""
    extras = extras or {}
    ctx = get_mesh_context()
    B, S = tokens.shape
    T = _cache_len(cfg, max_len)
    positions = jnp.arange(S)[None, :]
    x = _embed(params, tokens, cfg, extras)

    def block(carry, layer):
        x = carry
        p, is_local = layer
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.attn_type == "mla":
            a, (c_kv, k_rope) = mla_block(h, p["attn"], cfg, positions,
                                          extras, ctx)
            kv_out = (_fit_cache(c_kv, T, S), _fit_cache(k_rope, T, S))
        else:
            a, (k, v) = gqa_block(h, p["attn"], cfg, positions,
                                  _layer_window(cfg, is_local), extras, ctx)
            kv_out = (_fit_cache(k, T, S), _fit_cache(v, T, S))
        if "ln1_post" in p:
            a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, _ = mlp_block(h, p["mlp"], cfg, ctx)
        if "ln2_post" in p:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        return x + f, kv_out

    (x), kv_stacked = jax.lax.scan(
        block, x, (params["layers"], _local_flags(cfg)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x[:, -1:, :], cfg)[:, 0]

    if cfg.attn_type == "mla":
        kv = attn.MLACache(c_kv=kv_stacked[0], k_rope=kv_stacked[1])
    else:
        # slot i holds absolute position i (ring only matters past S >= T)
        pos_tags = jnp.broadcast_to(
            _prefill_positions(S, T), (cfg.n_layers, T))
        kv = attn.KVCache(k=kv_stacked[0], v=kv_stacked[1], positions=pos_tags)
    return logits, DecoderCache(kv=kv, length=jnp.asarray(S, jnp.int32))


def _prefill_positions(S: int, T: int) -> Array:
    """Position tags after prefilling S tokens into a length-T (ring) cache."""
    if S <= T:
        base = jnp.arange(T)
        return jnp.where(base < S, base, -1)
    # ring: slot i holds the latest position congruent to i (mod T)
    slots = jnp.arange(T)
    last_full = (S - 1) // T * T
    return jnp.where(slots <= (S - 1) % T, last_full + slots,
                     last_full - T + slots)


def _fit_cache(arr: Array, T: int, S: int) -> Array:
    """Fit per-layer fresh K/V (B,S,...) into a length-T cache buffer."""
    if S == T:
        return arr
    if S < T:
        pad = [(0, 0)] * arr.ndim
        pad[1] = (0, T - S)
        return jnp.pad(arr, pad)
    # S > T (ring): keep the last T entries, rolled so slot = pos % T
    tail = arr[:, S - T:]
    return jnp.roll(tail, shift=(S % T), axis=1)


# ---------------------------------------------------------------------------
# Paged serving: block-table prefill chunks + batched decode
# ---------------------------------------------------------------------------


def paged_supported(cfg: ModelConfig) -> tuple[bool, str]:
    """Whether the paged serving path can run this config.

    The paged kernel handles the plain GQA decoder (partial rotary and
    QKV bias included).  Features that change the attention pattern or
    the cache contents are routed to the dense path instead.
    """
    if cfg.family != "decoder":
        return False, f"family {cfg.family!r} has no paged cache layout"
    if cfg.attn_type == "mla":
        return False, "MLA latent cache not yet paged (ROADMAP follow-up)"
    if cfg.mrope or cfg.vision_tokens:
        return False, "multimodal position handling not paged"
    if cfg.sliding_window or cfg.local_global_period:
        return False, "sliding-window masks not paged"
    if cfg.attn_softcap:
        return False, "logit softcap not fused into the paged kernel"
    return True, ""


def _paged_scatter(kp, vp, k_new, v_new, blk, off):
    """Scatter per-token K/V into pool blocks.

    kp/vp: (nb, bs, Hkv, hd); k_new/v_new: (N, Hkv, hd); blk/off: (N,).
    Duplicate (blk, off) pairs only occur for lanes aimed at the null
    block (dead decode lanes, prefill pad tokens past the table
    extent), whose contents are never attended to.
    """
    kp = kp.at[blk, off].set(k_new.astype(kp.dtype))
    vp = vp.at[blk, off].set(v_new.astype(vp.dtype))
    return kp, vp


def decoder_prefill_chunk_paged(params, pool, tokens: Array, table: Array,
                                ctx_len: Array, cfg: ModelConfig
                                ) -> tuple[Array, Any]:
    """Prefill one chunk of one prompt into the paged pool.

    tokens: (1, c) int32 — chunk ``c`` is a static shape (the engine pads
    the last chunk so every chunk reuses one compiled program); ``table``
    (W,) int32 is the request's block table padded with the null block;
    ``ctx_len`` () int32 is the number of tokens already prefilled.

    Returns (logits (1, c, Vp), pool') — full-chunk logits so the host
    can read the last *real* prompt position of a padded final chunk.

    Correctness of attending over the whole gathered table: gathered slot
    ``i`` holds absolute position ``i`` for every live slot, and every
    garbage slot (null-block padding, stale pool contents past the
    chunk's end) sits at position > the last query position, so the
    causal mask removes it — no extra validity mask needed.

    Writes need one extra guard the mask can't provide: a padded final
    chunk can extend past the table extent (ceil(P/c)*c > W*bs), and a
    clamped gather of ``table`` would land those pad tokens in
    ``table[W-1]`` — an OWNED block when the request reserved full
    width — aliasing real positions.  Overflow writes are therefore
    routed to the null block explicitly.
    """
    from repro.models.attention import PagedKV

    ctx = get_mesh_context()
    _, c = tokens.shape
    W = table.shape[0]
    bs = pool.block_size
    positions = (ctx_len + jnp.arange(c))[None, :]                # (1, c)
    p_abs = ctx_len + jnp.arange(c)                               # (c,)
    word = p_abs // bs
    blk = jnp.where(word < W, table[jnp.minimum(word, W - 1)], 0)
    off = p_abs % bs
    x = _embed(params, tokens, cfg, {})

    def block(carry, layer):
        x = carry
        p, kp, vp = layer
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        pa = p["attn"]
        hd = cfg.hd
        q = h @ pa["wq"]
        k = h @ pa["wk"]
        v = h @ pa["wv"]
        if cfg.qkv_bias:
            q, k, v = q + pa["bq"], k + pa["bk"], v + pa["bv"]
        q = q.reshape(1, c, cfg.n_heads, hd)
        k = k.reshape(1, c, cfg.n_kv_heads, hd)
        v = v.reshape(1, c, cfg.n_kv_heads, hd)
        q, k = _rope_q_k(cfg, q, k, positions, {})
        kp, vp = _paged_scatter(kp, vp, k[0], v[0], blk, off)
        kg = kp[table].reshape(1, W * bs, cfg.n_kv_heads, hd)
        vg = vp[table].reshape(1, W * bs, cfg.n_kv_heads, hd)
        out = attn.blocked_attention(
            q, kg, vg, causal=True, q_offset=ctx_len,
            q_block=c, kv_block=bs)
        a = out.reshape(1, c, cfg.n_heads * hd) @ pa["wo"]
        if "ln1_post" in p:
            a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, _ = mlp_block(h, p["mlp"], cfg, ctx, serving=True)
        if "ln2_post" in p:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        return x + f, (kp, vp)

    x, kv_new = jax.lax.scan(block, x, (params["layers"], pool.k, pool.v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg)
    return logits, PagedKV(k=kv_new[0], v=kv_new[1])


def decoder_decode_step_paged(params, pool, token: Array, lengths: Array,
                              tables: Array, live: Array, cfg: ModelConfig
                              ) -> tuple[Array, Any]:
    """One decode wave over a batch of paged sequences.

    token: (B,) int32 last sampled tokens; lengths: (B,) int32 tokens
    already in each sequence's cache (the new token's position);
    tables: (B, W) int32 block tables padded with the null block; live:
    (B,) bool — dead lanes write to the null block and attend over zero
    keys, so their lane output is exactly zero instead of a full softmax
    over stale cache (the decode-waste fix, measured in test_serve.py).

    Returns (logits (B, Vp), pool').
    """
    from repro.kernels import ops as kernel_ops
    from repro.models.attention import PagedKV

    ctx = get_mesh_context()
    B = token.shape[0]
    bs = pool.block_size
    positions = lengths[:, None]                                  # (B, 1)
    blk = jnp.where(live, tables[jnp.arange(B), lengths // bs], 0)
    off = jnp.where(live, lengths % bs, 0)
    attend = jnp.where(live, lengths + 1, 0)                      # (B,)
    x = params["embed"][token][:, None, :]                        # (B, 1, d)
    if cfg.emb_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)

    def block(carry, layer):
        x = carry
        p, kp, vp = layer
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        pa = p["attn"]
        hd = cfg.hd
        q = h @ pa["wq"]
        k = h @ pa["wk"]
        v = h @ pa["wv"]
        if cfg.qkv_bias:
            q, k, v = q + pa["bq"], k + pa["bk"], v + pa["bv"]
        q = q.reshape(B, 1, cfg.n_heads, hd)
        k = k.reshape(B, 1, cfg.n_kv_heads, hd)
        v = v.reshape(B, 1, cfg.n_kv_heads, hd)
        q, k = _rope_q_k(cfg, q, k, positions, {})
        kp, vp = _paged_scatter(kp, vp, k[:, 0], v[:, 0], blk, off)
        out = kernel_ops.paged_attention(q[:, 0], kp, vp, tables, attend)
        a = out.reshape(B, 1, cfg.n_heads * hd) @ pa["wo"]
        if "ln1_post" in p:
            a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, _ = mlp_block(h, p["mlp"], cfg, ctx, serving=True)
        if "ln2_post" in p:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        return x + f, (kp, vp)

    x, kv_new = jax.lax.scan(block, x, (params["layers"], pool.k, pool.v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg)[:, 0]
    return logits, PagedKV(k=kv_new[0], v=kv_new[1])


def decoder_decode_step(params, cache: DecoderCache, token: Array,
                        cfg: ModelConfig, extras=None
                        ) -> tuple[Array, DecoderCache]:
    """One decode step: token (B,) int32 at position cache.length."""
    extras = extras or {}
    ctx = get_mesh_context()
    B = token.shape[0]
    pos = cache.length
    positions = jnp.full((B, 1), pos, jnp.int32)
    x = params["embed"][token][:, None, :]                        # (B,1,d)
    if cfg.emb_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    ring = isinstance(cache.kv, attn.KVCache) and True
    T = (cache.kv.k.shape[2] if isinstance(cache.kv, attn.KVCache)
         else cache.kv.c_kv.shape[2])
    use_ring = cfg.sliding_window and not cfg.local_global_period

    def block(carry, layer):
        x = carry
        if cfg.attn_type == "mla":
            p, is_local, c_c, kr_c = layer
        else:
            p, is_local, k_c, v_c, pos_c = layer
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        pa = p["attn"]
        if cfg.attn_type == "mla":
            m = cfg.mla
            dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
            cq = rms_norm(h @ pa["w_dq"], pa["q_norm"], cfg.norm_eps)
            q = (cq @ pa["w_uq"]).reshape(B, 1, cfg.n_heads, dn + dr)
            q_nope, q_rope = q[..., :dn], q[..., dn:]
            q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
            c_new = rms_norm(h @ pa["w_dkv"], pa["kv_norm"], cfg.norm_eps)
            kr_new = apply_rope((h @ pa["w_kr"])[:, :, None, :], positions,
                                cfg.rope_theta)[:, :, 0]
            c_c = jax.lax.dynamic_update_slice(
                c_c, c_new.astype(c_c.dtype), (0, pos, 0))
            kr_c = jax.lax.dynamic_update_slice(
                kr_c, kr_new.astype(kr_c.dtype), (0, pos, 0))
            out = attn.mla_decode_attention(
                q_nope[:, 0], q_rope[:, 0], c_c, kr_c,
                pa["w_uk"], pa["w_uv"], pos, softcap=cfg.attn_softcap)
            a = (out.reshape(B, 1, -1) @ pa["wo"])
            new_kv = (c_c, kr_c)
        else:
            hd = cfg.hd
            q = h @ pa["wq"]
            k = h @ pa["wk"]
            v = h @ pa["wv"]
            if cfg.qkv_bias:
                q, k, v = q + pa["bq"], k + pa["bk"], v + pa["bv"]
            q = q.reshape(B, 1, cfg.n_heads, hd)
            k = k.reshape(B, 1, cfg.n_kv_heads, hd)
            v = v.reshape(B, 1, cfg.n_kv_heads, hd)
            q, k = _rope_q_k(cfg, q, k, positions, extras)
            k_c, v_c, pos_c = attn.cache_write(
                k_c, v_c, pos_c, k, v, pos, ring=bool(use_ring))
            out = attn.decode_attention(
                q[:, 0], k_c, v_c, pos, cache_positions=pos_c,
                window=_layer_window(cfg, is_local),
                softcap=cfg.attn_softcap)
            a = out.reshape(B, 1, -1) @ pa["wo"]
            new_kv = (k_c, v_c, pos_c)
        if "ln1_post" in p:
            a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, _ = mlp_block(h, p["mlp"], cfg, ctx, serving=True)
        if "ln2_post" in p:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        return x + f, new_kv

    flags = _local_flags(cfg)
    if cfg.attn_type == "mla":
        xs = (params["layers"], flags, cache.kv.c_kv, cache.kv.k_rope)
    else:
        xs = (params["layers"], flags, cache.kv.k, cache.kv.v,
              cache.kv.positions)
    x, kv_new = jax.lax.scan(block, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg)[:, 0]
    if cfg.attn_type == "mla":
        kv = attn.MLACache(c_kv=kv_new[0], k_rope=kv_new[1])
    else:
        kv = attn.KVCache(k=kv_new[0], v=kv_new[1], positions=kv_new[2])
    return logits, DecoderCache(kv=kv, length=pos + 1)
