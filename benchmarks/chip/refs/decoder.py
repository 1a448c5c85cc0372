"""The dense decoder family: GQA and MLA decoders with a SwiGLU MLP, as the
configurations name it with ``"reference": "decoder"``.

A family module is what the harness knows of a model's shape.  It gives:

* ``param_shapes(conf)``: the program's parameter layout (stacked layers,
  ``(in, out)`` matrices, RMSNorm scales stored as offsets from 1), written
  out from the configuration's published sizes and checked against the
  program's own shapes before a run;
* ``stack_axes(path)``: a leaf's count of leading stack axes, by which the
  optimizer's slices are cut and named;
* ``init_scale(path, shape)``: the standard deviation of a seeded weight;
* ``program_keys(conf, cfg)``: the program's config fields this layout
  depends on, as (from the file, the program's) pairs;
* ``matmul_params(conf)`` and ``attn_dims(conf)``: the sizes model FLOPs
  are counted from (``flops.py``);
* ``Model``: the plain float32 forward and backward, split into pieces, that
  ``refs/subtrack.py`` trains;
* optionally ``EXTRA_SCOPES``: the program's scope names for this family's
  own layers, beside ``scopes.SCOPES``.

The reference is straightforward ``jax.numpy`` at matmul precision
``highest``: one sequence at a time, full causal softmax, no kernels,
cache or fusion.  It imports nothing of the system under test; it reads
the weights that ``model.make_params`` draws from the seed.

Blocks (the configuration file lists every departure from the published
model under ``assumed``):

* GQA (Qwen2): q, k, v projections with bias, RoPE on all head dims
  (pairs (i, i + hd/2)), causal softmax at 1/sqrt(hd), output projection,
  pre-norm residual, SwiGLU MLP.
* MLA (MiniCPM3 / DeepSeek-V2): q = W_uq rms(W_dq h); latent
  c = rms(W_dkv h) expanded to per-head keys (W_uk) and values (W_uv);
  one shared RoPE key W_kr h; softmax at 1/sqrt(nope + rope).

``fmt="fp8"`` rounds both operands of every matrix product, forward and
backward, to float8 e4m3 with a per-tensor scale before multiplying in
float32: the lower-precision control that the comparison must reject.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
TOP = ((), ())            # the pieces' key of the leaves outside the stack
LAYERS = ("layers",)


# ---------------------------------------------------------------------------
# Layout, seeded weights and sizes
# ---------------------------------------------------------------------------


def padded_vocab(conf: dict) -> int:
    step = int(conf["program"].get("vocab_round", 256))
    return -(-conf["vocab_size"] // step) * step


def attention_kind(conf: dict) -> str:
    return "mla" if "kv_lora_rank" in conf else "gqa"


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf[
        "num_attention_heads"]


def _map(fn, tree):
    if isinstance(tree, dict):
        return {k: _map(fn, v) for k, v in tree.items()}
    return fn(tree)


def param_shapes(conf: dict) -> dict:
    """Nested dict of shapes in the program's layout."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    V = padded_vocab(conf)
    if attention_kind(conf) == "mla":
        qr, kvr = conf["q_lora_rank"], conf["kv_lora_rank"]
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv = conf["v_head_dim"]
        attn = {"w_dq": (d, qr), "q_norm": (qr,), "w_uq": (qr, H * (dn + dr)),
                "w_dkv": (d, kvr), "kv_norm": (kvr,), "w_kr": (d, dr),
                "w_uk": (kvr, H, dn), "w_uv": (kvr, H, dv),
                "wo": (H * dv, d)}
    else:
        hd, Hkv = head_dim(conf), conf["num_key_value_heads"]
        attn = {"wq": (d, H * hd), "wk": (d, Hkv * hd), "wv": (d, Hkv * hd),
                "wo": (H * hd, d)}
        if conf["program"].get("qkv_bias"):
            attn.update(bq=(H * hd,), bk=(Hkv * hd,), bv=(Hkv * hd,))
    layer = {"ln1": (d,), "ln2": (d,), "attn": attn,
             "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}
    stack = lambda s: (L,) + s  # noqa: E731
    shapes = {"embed": (V, d),
              "layers": _map(stack, layer),
              "final_norm": (d,)}
    if not conf.get("tie_word_embeddings", False):
        shapes["lm_head"] = (d, V)
    return shapes


def stack_axes(path: tuple) -> int:
    """The layer stack's leaves have one leading stack axis."""
    return 1 if path[0] == LAYERS[0] else 0


def init_scale(path: tuple, shape: tuple) -> float:
    name = path[-1]
    if name in ("ln1", "ln2", "final_norm", "q_norm", "kv_norm"):
        return 0.05            # offsets of the RMSNorm gains from 1
    if name in ("bq", "bk", "bv"):
        return 0.1
    if name == "embed":
        return 0.02
    fan_in = shape[-3] if name in ("w_uk", "w_uv") else shape[-2]
    return 1.0 / math.sqrt(fan_in)


def program_keys(conf: dict, cfg) -> dict:
    """{field of the program's ModelConfig: (the file's value, the
    program's)} for the sizes this layout depends on."""
    out = {"d_ff": (conf["intermediate_size"], cfg.d_ff),
           "n_heads": (conf["num_attention_heads"], cfg.n_heads),
           "n_kv_heads": (conf["num_key_value_heads"], cfg.n_kv_heads),
           "attn_type": (attention_kind(conf), cfg.attn_type)}
    if cfg.mla is not None:
        for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim"):
            out[k] = (conf.get(k), getattr(cfg.mla, k))
    return out


def matmul_params(conf: dict) -> int:
    """Weights that multiply activations, per the published sizes: every
    layer's attention and MLP projections and the head over the published
    vocabulary (the program's padding of the vocabulary is not model
    work)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    if attention_kind(conf) == "mla":
        qr, kvr = conf["q_lora_rank"], conf["kv_lora_rank"]
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv = conf["v_head_dim"]
        attn = (d * qr + qr * H * (dn + dr) + d * kvr + d * dr
                + kvr * H * dn + kvr * H * dv + H * dv * d)
    else:
        hd, Hkv = head_dim(conf), conf["num_key_value_heads"]
        attn = 2 * d * H * hd + 2 * d * Hkv * hd
    return L * (attn + 3 * d * f) + d * conf["vocab_size"]


def attn_dims(conf: dict) -> tuple[int, int, int]:
    """(layers, heads * qk dim, heads * v dim)."""
    H = conf["num_attention_heads"]
    if attention_kind(conf) == "mla":
        qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        return conf["num_hidden_layers"], H * qk, H * conf["v_head_dim"]
    hd = head_dim(conf)
    return conf["num_hidden_layers"], H * hd, H * hd


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def quantize(x, fmt):
    if fmt is None:
        return x
    if fmt != "fp8":
        raise ValueError(f"unknown reference format {fmt!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(spec, a, b):
    return _einsum(spec, quantize(a, "fp8"), quantize(b, "fp8"))


def _einsum8_fwd(spec, a, b):
    qa, qb = quantize(a, "fp8"), quantize(b, "fp8")
    return _einsum(spec, qa, qb), (qa, qb)


def _einsum8_bwd(spec, res, g):
    qa, qb = res
    _, back = jax.vjp(partial(_einsum, spec), qa, qb)
    return back(quantize(g, "fp8"))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def product(spec, a, b, fmt):
    """einsum at ``highest``, or with float8 operands in both passes."""
    return _einsum(spec, a, b) if fmt is None else _einsum8(spec, a, b)


def mm(a, b, fmt):
    return product("...ij,jk->...ik", a, b, fmt)


def rms(x, gain_offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain_offset)


def rope(x, theta):
    """x: (S, H, d); position of row i is i."""
    S, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, d/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def attend(q, k, v, fmt):
    """Causal softmax attention.  q, k: (S, H, dk); v: (S, H, dv)."""
    S, dk = q.shape[0], q.shape[-1]
    scores = product("qhd,khd->hqk", q, k, fmt) / math.sqrt(dk)
    mask = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return product("hqk,khd->qhd", p, v, fmt)


def layer(w, x, conf, fmt):
    """One decoder block on one sequence x: (S, d)."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    H = conf["num_attention_heads"]
    S = x.shape[0]
    a = w["attn"]
    h = rms(x, w["ln1"], eps)
    if "kv_lora_rank" in conf:
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv, kvr = conf["v_head_dim"], conf["kv_lora_rank"]
        q = mm(rms(mm(h, a["w_dq"], fmt), a["q_norm"], eps), a["w_uq"], fmt)
        q = q.reshape(S, H, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], theta)
        c = rms(mm(h, a["w_dkv"], fmt), a["kv_norm"], eps)
        k_rope = rope(mm(h, a["w_kr"], fmt)[:, None, :], theta)
        k_nope = mm(c, a["w_uk"].reshape(kvr, H * dn), fmt).reshape(S, H, dn)
        val = mm(c, a["w_uv"].reshape(kvr, H * dv), fmt).reshape(S, H, dv)
        qk = jnp.concatenate([q_nope, q_rope], -1)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (S, H, dr))], -1)
        o = attend(qk, kk, val, fmt).reshape(S, H * dv)
    else:
        Hkv = conf["num_key_value_heads"]
        hd = conf["hidden_size"] // H
        q, k, v = (mm(h, a[n], fmt) for n in ("wq", "wk", "wv"))
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = rope(q.reshape(S, H, hd), theta)
        k = rope(k.reshape(S, Hkv, hd), theta)
        v = v.reshape(S, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        o = attend(q, k, v, fmt).reshape(S, H * hd)
    x = x + mm(o, a["wo"], fmt)
    m = w["mlp"]
    h = rms(x, w["ln2"], eps)
    g = jax.nn.silu(mm(h, m["w_gate"], fmt)) * mm(h, m["w_up"], fmt)
    return x + mm(g, m["w_down"], fmt)


def head_logits(w, x, conf, fmt):
    x = rms(x, w["final_norm"], conf["rms_norm_eps"])
    return mm(x, w["lm_head"][:, :conf["vocab_size"]], fmt)


def head_loss_sum(w, x, labels, weight, conf, fmt):
    """Weighted sum of next-token cross entropy of rows x (R, d)."""
    logp = jax.nn.log_softmax(head_logits(w, x, conf, fmt), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * weight)


def head_grads(w, x, labels, weight, conf, fmt, rows):
    """Loss sum and its gradients over rows x (N, d), ``rows`` at a time
    (N a multiple of ``rows``), accumulating the head's gradient."""
    n = x.shape[0] // rows
    xs = x.reshape(n, rows, -1)
    ls, ws = labels.reshape(n, rows), weight.reshape(n, rows)
    w = _f32(w)
    grad = jax.value_and_grad(partial(head_loss_sum, conf=conf, fmt=fmt),
                              argnums=(0, 1))

    def body(carry, inp):
        total, gw = carry
        loss, (gwc, gxc) = grad(w, *inp)
        return (total + loss, jax.tree.map(jnp.add, gw, gwc)), gxc

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, w))
    (total, gw), gx = jax.lax.scan(body, zero, (xs, ls, ws))
    return total, gw, gx.reshape(x.shape)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("rows",))
def _embed_grad(dx, tokens, rows):
    """The embedding's gradient: each position's input gradient added to
    its token's row (built in place)."""
    return jnp.zeros((rows, dx.shape[-1])).at[tokens].add(dx)


class Model:
    """Jitted per-layer pieces of the reference, built once per config.

    The weights are split into pieces keyed (prefix, index): the leaves
    outside the layer stack under ``TOP``, layer l's under
    ``(LAYERS, (l,))``; a leaf's path is the prefix and its path in the
    piece, and the index says which slice of its stack axes it is."""

    def __init__(self, conf, fmt=None, head_rows=512):
        self.conf, self.fmt, self.head_rows = conf, fmt, head_rows
        lay = jax.vmap(partial(layer, conf=conf, fmt=fmt), in_axes=(None, 0))
        self.fwd = jax.jit(lambda w, x: lay(_f32(w), x))

        def vjp(w, x, dy):
            _, back = jax.vjp(lay, _f32(w), x)
            return back(dy)

        self.bwd = jax.jit(vjp)

        self.head = jax.jit(partial(head_grads, conf=conf, fmt=fmt),
                            static_argnames=("rows",))

    def split(self, params) -> dict:
        """The program's stacked layout -> {key: piece's leaves}, in their
        stored dtype."""
        pieces = {TOP: {k: v for k, v in params.items() if k != LAYERS[0]}}
        for i in range(self.conf["num_hidden_layers"]):
            pieces[(LAYERS, (i,))] = jax.tree.map(lambda a, i=i: a[i],
                                                  params[LAYERS[0]])
        return pieces

    def embed(self, top, tokens):
        return top["embed"][tokens].astype(jnp.float32)

    def forward(self, top, layers, tokens):
        """Block inputs x_0 .. x_L for tokens (B, S)."""
        xs = [self.embed(top, tokens)]
        for w in layers:
            xs.append(self.fwd(w, xs[-1]))
        return xs

    def grads(self, pieces, tokens, rows=None):
        """Yields (key, g), g the gradient of some of that piece's leaves,
        nested as they are: the head's, then each layer's from the last,
        then the embedding's; finally (None, mean loss).  ``rows`` (S,)
        bool keeps only those positions in the mean (a fault: part of the
        batch left out)."""
        B, S = tokens.shape
        top = pieces[TOP]
        layers = [pieces[(LAYERS, (i,))]
                  for i in range(self.conf["num_hidden_layers"])]
        xs = self.forward(top, layers, tokens)
        labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        keep = np.arange(S) < S - 1
        if rows is not None:
            keep &= np.asarray(rows)
        count = B * int(keep.sum())
        # every position goes through the head (rows a multiple of the
        # chunk); the ones left out weigh 0
        weight = jnp.asarray(np.tile(keep, B) / count, jnp.float32)
        hw = {"final_norm": top["final_norm"], "lm_head": top["lm_head"]}
        total, g_head, gx = self.head(hw, xs[-1].reshape(B * S, -1),
                                      labels.reshape(-1), weight,
                                      rows=math.gcd(self.head_rows, B * S))
        total = float(total)
        dx = gx.reshape(xs[-1].shape)
        del gx
        yield TOP, g_head
        del g_head                       # one piece held at a time
        for i in range(len(layers) - 1, -1, -1):
            gw, dx = self.bwd(layers[i], xs[i], dx)
            xs[i + 1] = None
            yield (LAYERS, (i,)), gw
            del gw
        yield TOP, {"embed": _embed_grad(dx, tokens,
                                         rows=top["embed"].shape[0])}
        yield None, total
