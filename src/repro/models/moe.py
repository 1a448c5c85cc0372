"""Mixture-of-Experts layer with hybrid expert x tensor parallelism.

Production layout (DESIGN.md §5): activations are replicated across the
``model`` mesh axis (standard Megatron TP invariant at block entry), so no
token all-to-all is needed — each model rank computes *its* experts on the
tokens routed to them and the contributions merge in the same model-axis
all-reduce a TP FFN already performs.  The expert bank is stored
**physically pre-sharded** as ``(tp, E_loc, d, f_loc)`` where
``ep = gcd(E, tp)`` expert groups each tensor-shard their FFN hidden dim
``tp/ep`` ways (mixtral: 8 experts x 2-way; llama4: 16 groups x 8
experts/rank; CPU smoke: tp=1 degenerates to a single local bank).

Routing uses sort-free static-shape bucketing: per-expert capacity buffers
filled by cumsum-ranked scatter-add, with capacity-overflow tokens dropped
(GShard capacity factor).  Everything is differentiable (scatter-add /
gather / psum) and runs inside ``shard_map`` under the surrounding pjit.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import MeshContext, get_mesh_context
from repro.models.config import MoEConfig

Array = jax.Array


def moe_capacity(n_tokens_local: int, cfg: MoEConfig) -> int:
    c = math.ceil(n_tokens_local * cfg.top_k * cfg.capacity_factor
                  / cfg.n_experts)
    return max(8, c)


def init_moe_params(key, d_model: int, cfg: MoEConfig, ctx: MeshContext,
                    dtype=jnp.bfloat16) -> dict:
    """Expert bank in the physical (tp, E_loc, d, f_loc) layout + router."""
    from repro.models.common import dense_init, key_iter

    ep, e_loc, f_loc = ctx.expert_layout(cfg.n_experts, cfg.d_ff)
    tp = ctx.tp
    ks = key_iter(key)
    p = {
        "router": dense_init(next(ks), (d_model, cfg.n_experts),
                             dtype=jnp.float32),
        "wg": dense_init(next(ks), (tp, e_loc, d_model, f_loc), in_axis=-2,
                         dtype=dtype),
        "wu": dense_init(next(ks), (tp, e_loc, d_model, f_loc), in_axis=-2,
                         dtype=dtype),
        "wd": dense_init(next(ks), (tp, e_loc, f_loc, d_model), in_axis=-2,
                         dtype=dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.shared_d_ff * cfg.n_shared_experts
        p["shared_wg"] = dense_init(next(ks), (d_model, fs), dtype=dtype)
        p["shared_wu"] = dense_init(next(ks), (d_model, fs), dtype=dtype)
        p["shared_wd"] = dense_init(next(ks), (fs, d_model), dtype=dtype)
    return p


def _route(logits: Array, cfg: MoEConfig):
    """Top-k routing.  Returns (expert_ids (N,k), gates (N,k) fp32, probs)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, ids = jax.lax.top_k(logits.astype(jnp.float32), cfg.top_k)
    if cfg.top_k == 1:
        gates = jax.nn.sigmoid(vals)          # llama4-style single-expert gate
    else:
        gates = jax.nn.softmax(vals, axis=-1)  # mixtral-style renormalized
    return ids, gates, probs


def moe_layer(x: Array, params: dict, cfg: MoEConfig,
              ctx: MeshContext | None = None,
              serving: bool = False) -> tuple[Array, Array]:
    """Apply the MoE FFN.  x: (B, S, d) -> (y (B, S, d), aux_loss ()).

    Training/prefill: per-(pod,data)-shard token blocks, replicated over
    the model axis; expert weights FSDP-gathered per layer; psum over
    the model axis merges expert + within-expert-TP contributions.

    Serving (decode; §Perf it5): decode batches are tiny, so gathering
    multi-GB expert banks per token is the dominant cost.  Instead tokens
    replicate across the data axis and the expert FFN hidden dim shards
    over it — every weight stays resident (zero weight movement), each
    (model, data) rank computes its (expert-group, f-slice), and one psum
    over (model, data) merges.  Same math, measured on the decode cells.
    """
    ctx = ctx or get_mesh_context()
    cfgE, k = cfg.n_experts, cfg.top_k
    ep, e_loc, f_loc = ctx.expert_layout(cfgE, cfg.d_ff)
    tp_within = ctx.tp // ep
    B, S, d = x.shape
    # batch=1 decode (long_500k) can't shard over data: replicate tokens
    # across the data axis (each data rank computes the same single token).
    dp_ok = (B % ctx.dp == 0) and not serving
    n_local = (B // ctx.dp if dp_ok else B) * S
    C = moe_capacity(n_local, cfg)
    model_ax = ctx.model_axis
    batch_axes = ctx.batch_axes if dp_ok else ()
    tok_spec = P(batch_axes, None, None) if dp_ok else P(None, None, None)
    dp = ctx.dp
    f_shard_serving = serving and (f_loc % max(dp, 1) == 0) and dp > 1

    def body(xb, router, wg, wu, wd):
        # xb: (B_loc, S, d); wg/wu: (1, E_loc, d, f_loc); wd: (1, E_loc, f_loc, d)
        wg, wu, wd = wg[0], wu[0], wd[0]
        Bl = xb.shape[0]
        N = Bl * S
        xf = xb.reshape(N, d)
        logits = xf.astype(jnp.float32) @ router              # (N, E)
        ids, gates, probs = _route(logits, cfg)

        rank = jax.lax.axis_index(model_ax)
        group = rank // tp_within                              # expert group id
        my_base = group * e_loc                                # first global eid

        # --- bucket tokens into (E_loc, C) capacity buffers ---------------
        # slot-major ranking so capacity counts across the k routing slots
        eid_local = ids.T - my_base                            # (k, N)
        sel = (eid_local[:, :, None] ==
               jnp.arange(e_loc)[None, None, :])               # (k, N, E_loc)
        sel = sel.transpose(2, 0, 1).reshape(e_loc, k * N)     # (E_loc, k*N)
        ranks = jnp.cumsum(sel, axis=1) - 1                    # position in expert
        keep = sel & (ranks < C)
        scatter_pos = jnp.where(keep, ranks, C)                # C = overflow row
        scatter_pos = scatter_pos.reshape(e_loc, k, N)
        keep = keep.reshape(e_loc, k, N)

        buf = jnp.zeros((e_loc, C + 1, d), xb.dtype)
        for j in range(k):
            # scatter slot-j tokens into their expert's capacity row
            buf = jax.vmap(
                lambda b, idx, kp: b.at[idx].add(
                    jnp.where(kp[:, None], xf, 0)),
            )(buf, scatter_pos[:, j], keep[:, j])

        # --- expert FFN (SwiGLU) ------------------------------------------
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wu)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)            # partial over f_loc

        # --- combine back to token order ----------------------------------
        y = jnp.zeros((N, d), jnp.float32)
        tok = jnp.arange(N)
        for j in range(k):
            le = jnp.clip(ids[:, j] - my_base, 0, e_loc - 1)   # (N,)
            pos_j = scatter_pos[le, j, tok]                    # (N,)
            keep_j = keep[le, j, tok]                          # (N,)
            gathered = out_buf[le, pos_j]                      # (N, d)
            y += jnp.where(keep_j[:, None], gathered, 0
                           ).astype(jnp.float32) * gates[:, j][:, None]

        # merge experts + f shards; wire in bf16 (§Perf it4: the fp32
        # combine accumulator doesn't need fp32 on the network)
        axes = (model_ax,) + (tuple(ctx.batch_axes) if f_shard_serving
                              else ())
        y = jax.lax.psum(y.astype(xb.dtype), axes)
        return y.reshape(Bl, S, d)

    if f_shard_serving:
        # resident f-sharded banks: no gather, psum over (model, data)
        w_up_spec = P(model_ax, None, None, ctx.batch_axes)
        w_dn_spec = P(model_ax, None, ctx.batch_axes, None)
    else:
        w_up_spec = P(model_ax, None, None, None)
        w_dn_spec = P(model_ax, None, None, None)
    y = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(tok_spec, P(None, None),
                  w_up_spec, w_up_spec, w_dn_spec),
        out_specs=tok_spec,
        check_vma=False,
    )(x, params["router"], params["wg"], params["wu"], params["wd"])

    # --- auxiliary losses (computed on the global view; cheap) -------------
    logits = x.astype(jnp.float32).reshape(-1, d) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(logits, k)
    load = jnp.mean(jax.nn.one_hot(ids, cfgE, dtype=jnp.float32), axis=(0, 1))
    importance = jnp.mean(probs, axis=0)
    aux = cfgE * jnp.sum(load * importance) * cfg.aux_loss_coef
    z_loss = 1e-3 * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # --- shared (always-on) experts: plain TP SwiGLU ------------------------
    if "shared_wg" in params:
        from repro.distributed.context import shard
        h = jax.nn.silu(x @ params["shared_wg"]) * (x @ params["shared_wu"])
        h = shard(h, ctx.batch_axes, None, ctx.model_axis)
        y = y + h @ params["shared_wd"]

    return y, aux + z_loss
