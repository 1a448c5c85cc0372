"""Plain float32 reference of SubTrack++'s warm start and plain step, for
any model family of ``refs/``.

It follows SubTrack++ (arXiv:2502.01586, Alg. 1) for the steps before the
first subspace update.  S_0 spans the top-r left singular vectors of the
first gradient (``init`` "svd"), or the randomized range finder's
approximation of them (``init`` "randomized").  Each plain step projects
the clipped gradient, runs Adam on the projection, back-projects, and adds
the recovery term (Fira's column scaling of the orthogonal residual with
the Eq. 12 growth limiter).  Matrices whose smaller side is at most r take
plain Adam.  Parameters are stored in the configuration's bfloat16: each
step computes its update in float32 and rounds the new parameter to
bfloat16, as bf16-weight, fp32-state training does; the optimizer state is
float32.  The backward runs piece by piece and twice per step (once for the
global norm, once to apply), so that only one piece's gradient is held at
a time.

A slice is what the optimizer treats as one matrix: a leaf indexed by all
of its stack axes (the family's ``stack_axes``), named by its path and
those indices (``<path>.3`` for layer 3 of a stacked leaf, ``<path>.3.5``
for expert 5 of layer 3 of an expert bank).  The low-rank rule applies
per slice, as the program's optimizer applies it.  It imports nothing of
the system under test.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def is_lowrank(shape, rank) -> bool:
    """A slice of this shape takes the low-rank step."""
    return len(shape) == 2 and min(shape) > rank


def slice_name(path: tuple, index: tuple) -> str:
    return ".".join(path + tuple(str(i) for i in index))


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


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path, value):
    _get(tree, path[:-1])[path[-1]] = value


def train(model, stack_axes, params, batches, opt, rows=None):
    """Warm start on ``batches[0]`` and ``len(batches) - 1`` plain steps of
    a family's ``model`` (see ``refs/decoder.Model``) from ``params``, in
    the program's stacked layout; ``stack_axes(path)`` is the number of a
    leaf's leading stack axes.  ``rows`` (S,) bool keeps only those
    positions in the loss (a fault: part of the batch left out).

    Returns the loss of each step, the first step's global gradient norm
    (before the clip), the first clipped gradient's norm per slice as the
    optimizer holds it (projected for low-rank slices), the first raw
    gradient's norm per slice, each slice's parameter change after the
    last step, and the names of the dense-Adam slices.
    """
    pieces = model.split(params)
    del params                       # the stacked copy: only the split stays
    rank = opt["rank"]
    init = opt["init"]
    init = (init["method"],) + tuple(
        init[k] for k in ("seed", "oversample", "power_iters") if k in init)
    hp = (opt["beta1"], opt["beta2"], opt["eps"], opt["scale"], opt["zeta"])
    lr, clip_norm = opt["lr"], opt["clip_norm"]

    def slices(key, path, leaf):
        """(name, index within the leaf) of each slice of a piece's leaf."""
        prefix, index = key
        inner = stack_axes(prefix + path) - len(index)
        for idx in np.ndindex(leaf.shape[:inner]):
            yield slice_name(prefix + path, index + idx), idx

    state: dict = {}
    for key, g in model.grads(pieces, batches[0], rows):
        if key is None:
            continue
        for path, gl in _flat(g):
            for name, idx in slices(key, path, gl):
                gs = gl[idx] if idx else gl
                if is_lowrank(gs.shape, rank):
                    n = max(gs.shape)
                    state[name] = [_warm(gs, rank, init),
                                   jnp.zeros((rank, n)),
                                   jnp.zeros((rank, n)), jnp.float32(0.0)]
                else:
                    state[name] = [None, jnp.zeros(gs.shape),
                                   jnp.zeros(gs.shape)]

    P0, where, losses, first_grad, first_raw = {}, {}, [], {}, {}
    for t, tokens in enumerate(batches[1:], start=1):
        sq, loss = 0.0, None
        for key, g in model.grads(pieces, tokens, rows):
            if key is None:
                loss = g
            else:
                sq += _sqnorm(g)
        losses.append(loss)
        if t == 1:
            gnorm = math.sqrt(sq)
        cscale = min(1.0, clip_norm / max(math.sqrt(sq), 1e-12))
        for key, g in model.grads(pieces, tokens, rows):
            if key is None:
                continue
            home = pieces[key]
            for path, gl in _flat(g):
                _set(g, path, None)          # the step takes the buffer
                leaf = _get(home, path)
                for name, idx in slices(key, path, gl):
                    gs = gl[idx] if idx else gl
                    p = leaf[idx] if idx else leaf
                    if t == 1:
                        first_raw[name] = float(jnp.linalg.norm(gs))
                        P0[name], where[name] = p, (key, path, idx)
                    st = state[name]
                    args = (gs, jnp.float32(cscale), p)
                    gs = None
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
                    leaf = leaf.at[idx].set(p2) if idx else p2
                _set(home, path, leaf)
                del gl, leaf
            del g
    change = {}
    for name, p0 in P0.items():
        key, path, idx = where[name]
        p = _get(pieces[key], path)
        p = p[idx] if idx else p
        change[name] = float(jnp.linalg.norm(
            p.astype(jnp.float32) - p0.astype(jnp.float32)))
    return {"losses": losses, "gnorm": gnorm, "first_grad": first_grad,
            "first_raw": first_raw, "change": change,
            "dense": {n for n, st in state.items() if st[0] is None}}
