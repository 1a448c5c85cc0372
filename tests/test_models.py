"""Model-zoo tests: per-arch reduced-config smoke (forward/train step, output
shapes, no NaNs — assignment requirement), serving-path consistency
(prefill+decode == full forward), and cell-level math checks (blocked
attention vs naive, SSD chunked vs recurrent, mLSTM chunked vs step)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED_ARCHS, PAPER_RANKS, get_config
from repro.models import attention as attn
from repro.models import ssm, xlstm
from repro.models.api import SHAPE_GRID, build_model, shape_applicable
from repro.models.config import SSMConfig


def _batch_for(cfg, key, B, S):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.vision_tokens:
        batch["vision_embeds"] = 0.02 * jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
        batch["mrope_positions"] = jnp.stack([pos] * 3, axis=1)
    if cfg.family == "encdec":
        batch["frames"] = 0.1 * jax.random.normal(key, (B, S, cfg.d_model),
                                                  jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", sorted(PAPER_RANKS))
def test_paper_ladder_heads_tile_d_model(arch):
    """Every paper-ladder Llama has n_heads * head_dim == d_model (the
    q/k/v/o leaves are d_model wide, as in every LLaMA implementation)."""
    cfg = get_config(arch)
    assert cfg.n_heads * cfg.hd == cfg.d_model


def test_llama_1b_matches_galore_config():
    """The 1B ladder entry follows GaLore's llama_1b.json: 24 layers,
    32 heads, hidden 2048, intermediate 5461."""
    cfg = get_config("llama-1b")
    assert (cfg.n_layers, cfg.n_heads, cfg.d_model, cfg.d_ff) == \
        (24, 32, 2048, 5461)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_arch_smoke_train_step(arch, rng):
    """Assignment: reduced config, one forward/train step on CPU,
    output shapes + no NaNs."""
    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init(rng)
    B, S = 2, 32
    batch = _batch_for(cfg, rng, B, S)
    loss, metrics = bundle.loss(params, batch, remat="none")
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: loss not finite"
    grads = jax.grad(lambda p: bundle.loss(p, batch)[0])(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), \
            f"{arch}: NaN grad at {path}"
    # one optimizer step moves the loss
    from repro.core.api import get_optimizer
    opt = get_optimizer("subtrack", rank=8, update_interval=4)
    state = opt.warm_start(opt.init(params), grads)
    u, _ = opt.update(grads, state, params, 1e-3)
    p2 = jax.tree.map(lambda a, b: a + b, params, u)
    loss2, _ = bundle.loss(p2, batch, remat="none")
    assert bool(jnp.isfinite(loss2))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_arch_prefill_decode_consistency(arch, rng):
    """Serving path correctness: teacher-forced decode after prefill must
    reproduce the full-forward logits at each position."""
    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init(rng)
    B, S, extra = 2, 16, 4
    batch = _batch_for(cfg, rng, B, S + extra)
    toks = batch["tokens"]

    # ground truth: full forward logits
    if cfg.family == "decoder":
        from repro.models.transformer import decoder_forward
        extras = {k: v for k, v in batch.items() if k != "tokens"}
        full_logits, _ = decoder_forward(params, toks, cfg, extras,
                                         remat="none")
    elif cfg.family == "zamba":
        from repro.models.zamba import zamba_forward
        full_logits, _ = zamba_forward(params, toks, cfg, remat="none")
    elif cfg.family == "xlstm":
        from repro.models.xlstm import xlstm_forward
        full_logits, _ = xlstm_forward(params, toks, cfg, remat="none")
    else:
        from repro.models.encdec import decode_train, encode
        memory = encode(params, batch["frames"], cfg, remat="none")
        full_logits = decode_train(params, memory, toks, cfg, remat="none")

    # serving path: prefill S, then teacher-force `extra` decode steps
    pf_batch = {k: (v[:, :S] if k in ("tokens", "mrope_positions") else v)
                for k, v in batch.items()}
    if cfg.mrope:
        pf_batch["mrope_positions"] = batch["mrope_positions"][..., :S]
    if cfg.family == "encdec":
        pf_batch["frames"] = batch["frames"]
    logits, cache = bundle.prefill(params, pf_batch, max_len=S + extra)

    # bf16 params + different contraction orders (and MoE routing can flip
    # on ties) => statistical agreement, not bitwise:
    #   (a) overwhelming argmax agreement, (b) tight p90 logit deltas.
    got = [np.asarray(logits, np.float32)]
    want = [np.asarray(full_logits[:, S - 1], np.float32)]
    for i in range(extra):
        logits, cache = bundle.decode_step(params, cache, toks[:, S + i])
        got.append(np.asarray(logits, np.float32))
        want.append(np.asarray(full_logits[:, S + i], np.float32))
    got_a, want_a = np.stack(got), np.stack(want)
    agree = (got_a.argmax(-1) == want_a.argmax(-1)).mean()
    p90 = np.percentile(np.abs(got_a - want_a), 90)
    scale = np.percentile(np.abs(want_a), 90) + 1e-3
    assert agree >= 0.9, f"{arch}: argmax agreement {agree:.2f}"
    assert p90 < 0.12 * scale + 0.12, \
        f"{arch}: p90 logit delta {p90:.3f} (scale {scale:.3f})"


def test_shape_grid_covers_40_cells():
    rows = [(a, s) for a in ASSIGNED_ARCHS for s in SHAPE_GRID]
    assert len(rows) == 40
    skips = [(a, s) for a, s in rows
             if not shape_applicable(get_config(a), SHAPE_GRID[s])[0]]
    # long_500k skipped exactly for the 7 full-attention archs
    assert len(skips) == 7
    assert all(s == "long_500k" for _, s in skips)
    runnable_long = {a for a, s in rows
                     if s == "long_500k" and (a, s) not in skips}
    assert runnable_long == {"zamba2-7b", "xlstm-125m", "mixtral-8x22b"}


class TestBlockedAttention:
    def _naive(self, q, k, v, causal=True, window=None, softcap=0.0):
        B, S, H, hd = q.shape
        Hkv = k.shape[2]
        G = H // Hkv
        qg = q.reshape(B, S, Hkv, G, hd)
        logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
        logits /= jnp.sqrt(hd)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        qp = jnp.arange(S)[:, None]
        kp = jnp.arange(S)[None, :]
        mask = jnp.ones((S, S), bool)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        logits = jnp.where(mask, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bkgqd", w.astype(v.dtype), v)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, -1)

    @pytest.mark.parametrize("window,softcap,Hkv", [
        (None, 0.0, 4), (8, 0.0, 2), (None, 30.0, 4), (16, 50.0, 1),
    ])
    def test_matches_naive(self, window, softcap, Hkv):
        key = jax.random.PRNGKey(0)
        B, S, H, hd = 2, 64, 4, 16
        q = jax.random.normal(key, (B, S, H, hd))
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
        got = attn.blocked_attention(q, k, v, causal=True, window=window,
                                     softcap=softcap, q_block=16, kv_block=32)
        want = self._naive(q, k, v, True, window, softcap)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-2)

    def test_decode_matches_last_row_of_prefill(self):
        key = jax.random.PRNGKey(1)
        B, S, H, hd = 2, 32, 4, 16
        q = jax.random.normal(key, (B, S, H, hd))
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
        full = attn.blocked_attention(q, k, v, q_block=8, kv_block=8)
        got = attn.decode_attention(q[:, -1], k, v, jnp.int32(S - 1))
        np.testing.assert_allclose(got, full[:, -1], atol=2e-3, rtol=1e-2)

    def test_ring_buffer_window_decode(self):
        """Ring cache slots hold out-of-order positions; windowed decode
        must still equal attention over the true last-W tokens."""
        key = jax.random.PRNGKey(2)
        B, H, hd, W = 1, 2, 8, 8
        total = 20
        ks = jax.random.normal(key, (B, total, H, hd))
        vs = jax.random.normal(jax.random.fold_in(key, 1), (B, total, H, hd))
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, H, hd))
        # fill a ring cache with positions 0..total-1
        k_c = jnp.zeros((B, W, H, hd))
        v_c = jnp.zeros((B, W, H, hd))
        pos_c = jnp.full((W,), -1, jnp.int32)
        for p in range(total):
            k_c, v_c, pos_c = attn.cache_write(
                k_c, v_c, pos_c, ks[:, p:p+1], vs[:, p:p+1],
                jnp.int32(p), ring=True)
        pos = total - 1
        got = attn.decode_attention(q, k_c, v_c, jnp.int32(pos),
                                    cache_positions=pos_c, window=W)
        want = attn.decode_attention(
            q, ks[:, total - W:], vs[:, total - W:], jnp.int32(W - 1))
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-2)


class TestSSD:
    def test_chunked_matches_recurrence(self):
        """ssd_chunked == exact step-by-step recurrence."""
        key = jax.random.PRNGKey(3)
        B, S, H, P, N = 2, 32, 3, 8, 4
        x = jax.random.normal(key, (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(
            jax.random.fold_in(key, 1), (B, S, H)))
        A = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (H,)))
        Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N))
        Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N))
        y_chunk, h_chunk = ssm.ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
        h = jnp.zeros((B, H, P, N))
        ys = []
        for t in range(S):
            y_t, h = ssm.ssd_decode_step(x[:, t], dt[:, t], A, Bm[:, t],
                                         Cm[:, t], h)
            ys.append(y_t)
        y_seq = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(y_chunk, y_seq, atol=1e-3, rtol=1e-2)
        np.testing.assert_allclose(h_chunk, h, atol=1e-3, rtol=1e-2)

    def test_chunk_boundary_invariance(self):
        key = jax.random.PRNGKey(4)
        B, S, H, P, N = 1, 24, 2, 4, 4
        x = jax.random.normal(key, (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(
            jax.random.fold_in(key, 1), (B, S, H)))
        A = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (H,)))
        Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N))
        Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N))
        y1, _ = ssm.ssd_chunked(x, dt, A, Bm, Cm, chunk=4)
        y2, _ = ssm.ssd_chunked(x, dt, A, Bm, Cm, chunk=12)
        np.testing.assert_allclose(y1, y2, atol=1e-3, rtol=1e-2)


class TestMLSTM:
    def test_chunked_matches_stepwise(self):
        key = jax.random.PRNGKey(5)
        B, S, H, hd = 2, 16, 2, 8
        mk = jax.random.split(key, 5)
        q = jax.random.normal(mk[0], (B, S, H, hd))
        k = jax.random.normal(mk[1], (B, S, H, hd))
        v = jax.random.normal(mk[2], (B, S, H, hd))
        log_i = jax.random.normal(mk[3], (B, S, H))
        log_f = jax.nn.log_sigmoid(jax.random.normal(mk[4], (B, S, H)) + 1.0)
        y_chunk, st_chunk = xlstm.mlstm_chunked(q, k, v, log_i, log_f,
                                                chunk=4)
        st = xlstm.init_mlstm_state(B, H, hd)
        ys = []
        for t in range(S):
            y_t, st = xlstm.mlstm_decode(q[:, t], k[:, t], v[:, t],
                                         log_i[:, t], log_f[:, t], st)
            ys.append(y_t)
        y_seq = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(y_chunk, y_seq, atol=2e-3, rtol=2e-2)
        np.testing.assert_allclose(st_chunk.C, st.C, atol=2e-3, rtol=2e-2)
