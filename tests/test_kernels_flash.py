"""Training attention's fused flash kernel (forward and backward) against
the blocked-attention oracle, in interpret mode at tiny sizes (S 256,
blocks 128), and the dispatch that chooses between them
(``repro.models.attention.self_attention``)."""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import block_for, flash_attention
from repro.models.attention import blocked_attention, self_attention

S = 256


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    assert ops._mode() == "interpret"


def _qkv(Hq, Hkv, hd=128, vd=None, dtype=jnp.float32, B=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, Hq, hd), dtype),
            jax.random.normal(ks[1], (B, S, Hkv, hd), dtype),
            jax.random.normal(ks[2], (B, S, Hkv, vd or hd), dtype))


def _blocked(q, k, v, **kw):
    return blocked_attention(q, k, v, q_block=128, kv_block=128, **kw)


HEADS = {"mha": (2, 2), "gqa": (4, 2), "mqa": (4, 1)}
MASKS = {"causal": {}, "window": {"window": 100}, "softcap": {"softcap": 30.0}}


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("heads", HEADS)
def test_forward_matches_blocked(interpret, heads, mask):
    q, k, v = _qkv(*HEADS[heads], B=2 if heads == "gqa" else 1)
    got = self_attention(q, k, v, **MASKS[mask])
    want = _blocked(q, k, v, **MASKS[mask])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def _grads(fn, q, k, v):
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape[:3] + v.shape[3:])

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", [
    dict(heads=(2, 2)),                                   # MHA, hd 128
    dict(heads=(2, 2), hd=96, vd=64),                     # MLA widths
    dict(heads=(4, 2), window=100, softcap=30.0),         # GQA, both
])
def test_grad_matches_blocked(interpret, case):
    kw = {k: case[k] for k in ("window", "softcap") if k in case}
    q, k, v = _qkv(*case["heads"], hd=case.get("hd", 128), vd=case.get("vd"))
    got = _grads(lambda *a: self_attention(*a, **kw), q, k, v)
    want = _grads(lambda *a: _blocked(*a, **kw), q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()),
                                   rtol=1e-4)


def test_bf16_forward_and_grad_match_blocked(interpret):
    """The chip's operand dtype: bf16 q/k/v, float32 statistics; results
    agree with the oracle to bf16 rounding."""
    q, k, v = _qkv(2, 2, dtype=jnp.bfloat16)
    got = (self_attention(q, k, v),) + _grads(self_attention, q, k, v)
    want = (_blocked(q, k, v),) + _grads(_blocked, q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 2e-2 * np.abs(w).max()


@pytest.mark.parametrize("seq,block", [(128, 128), (256, 128), (384, 128),
                                       (1024, 512), (2048, 512),
                                       (32768, 512)])
def test_block_for(seq, block):
    assert block_for(seq) == block


def _paths(monkeypatch, fn, *shapes):
    """Trace ``fn`` as on the chip (compiled mode) -> the dispatch tally's
    (entry, path) pairs; nothing is compiled or run."""
    monkeypatch.setattr(ops, "_mode", lambda: "compiled")
    ops.reset_dispatch_tally()
    jax.eval_shape(fn, *[jax.ShapeDtypeStruct(s, jnp.bfloat16)
                         for s in shapes])
    tally = ops.dispatch_tally()
    ops.reset_dispatch_tally()
    return {(entry, path): shapes for entry, path, shapes in tally}


QKV = [(1, S, 4, 128), (1, S, 2, 128), (1, S, 2, 128)]


def test_kernel_path_is_tallied(monkeypatch):
    paths = _paths(monkeypatch, lambda *a: _grads(self_attention, *a), *QKV)
    assert paths == {("flash_attention", ops.KERNEL): tuple(QKV)}


def test_traced_window_takes_the_reference_path(monkeypatch):
    """gemma2's alternation passes a traced per-layer window: the kernel
    takes only a static one."""
    def fn(q, k, v):
        return self_attention(q, k, v, window=jnp.where(True, 64, 1 << 30))

    assert _paths(monkeypatch, fn, *QKV) == {
        ("flash_attention", ops.REFERENCE): tuple(QKV)}


def test_prefill_continuation_takes_the_reference_path(monkeypatch, capsys):
    """A chunk that continues a prefix (q_offset > 0, T > S) is not
    self-attention of one sequence."""
    q, kv = (1, 128, 4, 128), (1, S, 2, 128)

    def fn(q, k, v):
        return self_attention(q, k, v, q_offset=128)

    assert _paths(monkeypatch, fn, q, kv, kv) == {
        ("flash_attention", ops.REFERENCE): (q, kv, kv)}
    assert "flash_attention" in capsys.readouterr().out


def test_backward_kernels_land_in_the_callers_scope(interpret):
    """The custom_vjp's backward is traced under its call site's name stack:
    inside a layer's ``attention`` scope its kernels read as the attention
    layer's backward pass (``benchmarks/chip/scopes.py``)."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.chip import scopes

    def loss(q, k, v):
        with jax.named_scope("attention"):
            return jnp.sum(self_attention(q, k, v).astype(jnp.float32))

    q, k, v = _qkv(2, 2)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    found = {}
    for name in re.findall(r'op_name="([^"]*)"', text):
        kernel = re.search(r"flash_(fwd|dq|dkv)", name)
        if kernel:
            found.setdefault(kernel.group(0), set()).add(
                scopes.scope_of(name))
    assert found == {"flash_fwd": {("attention", "forward")},
                     "flash_dq": {("attention", "backward")},
                     "flash_dkv": {("attention", "backward")}}


def test_sequence_off_the_lane_grid_is_refused():
    q, k, v = (jnp.zeros((1, 200, 2, 128)),) * 3
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True)
