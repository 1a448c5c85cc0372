"""Plain float32 reference of the decoders the configurations describe, and
of SubTrack++'s warm start and plain step.

Straightforward ``jax.numpy`` at matmul precision ``highest``: one
sequence at a time, full causal softmax, no kernels, cache or fusion.  It
imports nothing of the system under test; it reads the weights that
``model.make_params`` draws from the seed, in the layout documented there
(RMSNorm gains are stored as offsets from 1).

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

The optimizer part follows SubTrack++ (arXiv:2502.01586, Alg. 1) for the
steps before the first subspace update.  S_0 spans the top-r left
singular vectors of the first gradient (``init`` "svd"), or the randomized
range finder's approximation of them (``init`` "randomized").  Each plain
step projects the clipped gradient, runs Adam on the projection,
back-projects, and adds the recovery term (Fira's column scaling of the
orthogonal residual with the Eq. 12 growth limiter).  Matrices whose
smaller side is at most r take plain Adam.  Parameters are stored in the
configuration's bfloat16: each step computes its update in float32 and
rounds the new parameter to bfloat16, as bf16-weight, fp32-state training
does; the optimizer state is float32.  The backward runs layer by layer
and twice per step (once for the global norm, once to apply), so that
only one layer's gradient is held at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


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


# ---------------------------------------------------------------------------
# Weights: the program's stacked layout -> per-layer pieces
# ---------------------------------------------------------------------------


def split_layers(params) -> tuple[dict, list[dict]]:
    """(non-layer leaves, [layer l's leaves]), in their stored dtype."""
    top = {k: v for k, v in params.items() if k != "layers"}
    n = jax.tree.leaves(params["layers"])[0].shape[0]
    layers = [jax.tree.map(lambda a, i=i: a[i], params["layers"])
              for i in range(n)]
    return top, layers


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("rows",))
def _embed_grad(dx, tokens, rows):
    """The embedding's gradient: each position's input gradient added to
    its token's row (built in place)."""
    return jnp.zeros((rows, dx.shape[-1])).at[tokens].add(dx)


class Model:
    """Jitted per-layer pieces of the reference, built once per config."""

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

    def embed(self, top, tokens):
        return top["embed"][tokens].astype(jnp.float32)

    def forward(self, top, layers, tokens):
        """Block inputs x_0 .. x_L for tokens (B, S)."""
        xs = [self.embed(top, tokens)]
        for w in layers:
            xs.append(self.fwd(w, xs[-1]))
        return xs

    def grads(self, top, layers, tokens, rows=None):
        """Yields ("head", g) ... ("layer", l, g) ... ("embed", g) and
        finally ("loss", mean).  ``rows`` (S,) bool keeps only those
        positions in the mean (a fault: part of the batch left out)."""
        B, S = tokens.shape
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
        yield ("head", g_head)
        del g_head                       # one piece held at a time
        for i in range(len(layers) - 1, -1, -1):
            gw, dx = self.bwd(layers[i], xs[i], dx)
            xs[i + 1] = None
            yield ("layer", i, gw)
            del gw
        yield ("embed", _embed_grad(dx, tokens, rows=top["embed"].shape[0]))
        yield ("loss", total)


# ---------------------------------------------------------------------------
# SubTrack++ before its first subspace update
# ---------------------------------------------------------------------------


def is_lowrank(shape, rank) -> bool:
    return len(shape) == 2 and min(shape) > rank


def _canon(g):
    return g.T if g.shape[0] > g.shape[1] else g


def top_subspace(G, rank):
    """Top-r left singular vectors of G (m <= n): eigenvectors of G G^T."""
    w, U = jnp.linalg.eigh(jnp.matmul(G, G.T, precision=HIGHEST))
    return U[:, ::-1][:, :rank]


def range_finder(G, rank, seed, oversample, power_iters):
    """Halko, Martinsson and Tropp's randomized range finder: the first r
    columns of orth((G G^T)^q G Omega), Omega (n, r + p) standard normal
    from ``jax.random.PRNGKey(seed)``."""
    m, n = G.shape
    k = min(rank + oversample, m)
    omega = jax.random.normal(jax.random.PRNGKey(seed), (n, k), jnp.float32)
    Y = jnp.matmul(G, omega, precision=HIGHEST)
    for _ in range(power_iters):
        Y = jnp.matmul(G, jnp.matmul(G.T, Y, precision=HIGHEST),
                       precision=HIGHEST)
    Q, _ = jnp.linalg.qr(Y)
    return Q[:, :rank]


@partial(jax.jit, static_argnames=("rank", "init"))
def _warm(g, rank, init):
    """S_0 from the first gradient: ``init`` is ("svd",) or
    ("randomized", seed, oversample, power_iters)."""
    G = _canon(g)
    if init[0] == "svd":
        return top_subspace(G, rank)
    return range_finder(G, rank, *init[1:])


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(0,))
def _lowrank_step(g, cscale, p, S, M, V, lam_prev, t, lr, hp):
    b1, b2, eps, scale, zeta = hp
    G = _canon(g) * cscale
    Gt = jnp.matmul(S.T, G, precision=HIGHEST)
    M = b1 * M + (1 - b1) * Gt
    V = b2 * V + (1 - b2) * Gt * Gt
    m_hat = M / (1 - b1 ** t)
    v_hat = V / (1 - b2 ** t)
    Gto = m_hat / (jnp.sqrt(v_hat) + eps)
    phi = jnp.linalg.norm(Gto, axis=0) / jnp.maximum(
        jnp.linalg.norm(Gt, axis=0), 1e-30)
    Lam = (G - jnp.matmul(S, Gt, precision=HIGHEST)) * phi[None, :]
    lam = jnp.linalg.norm(Lam)
    limit = zeta * lam_prev
    clip = jnp.where((lam_prev > 0) & (lam > limit),
                     limit / jnp.maximum(lam, 1e-30), 1.0)
    lam_new = jnp.where(lam_prev > 0, jnp.minimum(lam, limit), lam)
    upd = -lr * scale * (jnp.matmul(S, Gto, precision=HIGHEST) + clip * Lam)
    upd = upd.T if g.shape[0] > g.shape[1] else upd
    return (p + upd).astype(p.dtype), M, V, lam_new, jnp.linalg.norm(Gt)


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(0,))
def _dense_step(g, cscale, p, M, V, t, lr, hp):
    b1, b2, eps = hp[:3]
    g = g * cscale
    M = b1 * M + (1 - b1) * g
    V = b2 * V + (1 - b2) * g * g
    upd = -lr * (M / (1 - b1 ** t)) / (jnp.sqrt(V / (1 - b2 ** t)) + eps)
    return (p + upd).astype(p.dtype), M, V, jnp.linalg.norm(g)


def _sqnorm(tree) -> float:
    return float(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def _flat(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = value


def _name(kind, i, path):
    return ("layers." + ".".join(path) + f".{i}" if kind == "layer"
            else ".".join(path))


def train(params, batches, conf, opt, fmt=None, rows=None):
    """Warm start on ``batches[0]`` and ``len(batches) - 1`` plain steps.

    Returns the loss of each step, the first step's global gradient norm
    (before the clip), the first clipped gradient's norm per slice as the
    optimizer holds it (projected for low-rank slices), the first raw
    gradient's norm per slice, each slice's parameter change after the
    last step, and the names of the dense-Adam slices.  A slice is one layer's piece of a stacked leaf.
    """
    model = Model(conf, fmt)
    top, layers = split_layers(params)
    del params                       # the stacked copy: only the split stays
    rank = opt["rank"]
    init = opt["init"]
    init = (init["method"],) + tuple(
        init[k] for k in ("seed", "oversample", "power_iters") if k in init)
    hp = (opt["beta1"], opt["beta2"], opt["eps"], opt["scale"], opt["zeta"])
    lr, clip_norm = opt["lr"], opt["clip_norm"]

    def owner(kind, i):
        """The dict the piece's weights live in, and the piece's paths."""
        return layers[i] if kind == "layer" else top

    def pieces(gen):
        for item in gen:
            if item[0] == "loss":
                continue
            if item[0] == "layer":
                yield "layer", item[1], item[2]
            elif item[0] == "embed":
                yield "embed", None, {"embed": item[1]}
            else:
                yield item[0], None, item[1]

    state: dict = {}
    for kind, i, g in pieces(model.grads(top, layers, batches[0], rows)):
        for path, gs in _flat(g):
            if is_lowrank(gs.shape, rank):
                n = max(gs.shape)
                state[_name(kind, i, path)] = [
                    _warm(gs, rank, init), jnp.zeros((rank, n)),
                    jnp.zeros((rank, n)), jnp.float32(0.0)]
            else:
                state[_name(kind, i, path)] = [
                    None, jnp.zeros(gs.shape), jnp.zeros(gs.shape)]

    P0, losses, first_grad, first_raw = {}, [], {}, {}
    for t, tokens in enumerate(batches[1:], start=1):
        sq, loss = 0.0, None
        for item in model.grads(top, layers, tokens, rows):
            if item[0] == "loss":
                loss = item[1]
            else:
                sq += _sqnorm(item[-1])
        losses.append(loss)
        if t == 1:
            gnorm = math.sqrt(sq)
        cscale = min(1.0, clip_norm / max(math.sqrt(sq), 1e-12))
        for kind, i, g in pieces(model.grads(top, layers, tokens, rows)):
            home = owner(kind, i)
            for path, gs in _flat(g):
                name = _name(kind, i, path)
                p = home
                for k in path:
                    p = p[k]
                if t == 1:
                    first_raw[name] = float(jnp.linalg.norm(gs))
                    P0[name] = p
                st = state[name]
                args = (gs, jnp.float32(cscale), p)
                gs = None
                _set(g, path, None)          # the step takes the buffer
                if st[0] is not None:
                    p2, st[1], st[2], st[3], gn = _lowrank_step(
                        *args, st[0], st[1], st[2], st[3],
                        jnp.float32(t), jnp.float32(lr), hp)
                else:
                    p2, st[1], st[2], gn = _dense_step(
                        *args, st[1], st[2], jnp.float32(t),
                        jnp.float32(lr), hp)
                del args
                if t == 1:
                    first_grad[name] = float(gn)
                _set(home, path, p2)
            del g, gs
    change = {}
    for name, p0 in P0.items():
        path = tuple(name.split("."))
        if path[0] == "layers":
            p = layers[int(path[-1])]
            path = path[1:-1]
        else:
            p = top
        for k in path:
            p = p[k]
        change[name] = float(jnp.linalg.norm(
            p.astype(jnp.float32) - p0.astype(jnp.float32)))
    return {"losses": losses, "gnorm": gnorm, "first_grad": first_grad,
            "first_raw": first_raw, "change": change,
            "dense": {n for n, st in state.items() if st[0] is None}}

