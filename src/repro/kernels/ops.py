"""Jit'd dispatch layer over the Pallas kernels.

``repro.core.lowrank_adam`` calls these entry points when the optimizer
is built with ``use_kernels=True``.  The fused hot path uses exactly
three per non-tracking step:

    project_colnorms(S, G)       -> ((r, n), (n,))  one read of G
    adam_lowrank_norms(...)      -> (M', V', Gto, gt_sq, gto_sq)  (r, n) pass
    fused_update(...)            -> (m, n) final-dtype update  one read of G

The 1-of-k tracking step swaps the first launch for the fused
subspace-update front end and reuses the same epilogue:

    project_tangent_colnorms(S, G) -> (A, gsq, T)   one read of G (single
                                      launch for m <= MAX_FUSED_TANGENT_M,
                                      else project_colnorms + tangent)
    project(S_new, G)              -> (r, n)        one read of G (gsq is
                                      basis-independent, so the norms from
                                      the first launch are reused)
    adam_lowrank_norms + fused_update as above

The unfused building blocks remain as baselines and fallbacks:

    backproject(S, X)       -> (m, n)
    recovery(S, G, Gt, phi) -> (m, n)
    tangent(G, A, S)        -> (m, r)

Dispatch policy: on TPU the Pallas kernels run compiled; on CPU they run
in interpret mode only when REPRO_FORCE_KERNELS=1 (tests do this —
interpret mode is a correctness tool, not a performance path), otherwise
the pure-jnp reference executes.  The kernels take any column count n
(a ragged last column block; see repro.kernels.grassmann), so the 1B
ladder model's d_ff = 5461 leaves run them; a row extent m (or rank r for
the Adam kernels) that does not tile its block falls back to the
reference, which keeps odd user models working.  In compiled mode every
dispatch is recorded per (entry point, path, shapes) at trace time — path is
``kernel``, ``composite`` (several launches instead of the fused one) or
``reference`` — and the first trace of each fallback is printed, so a
fallback on the chip is never silent (:func:`dispatch_tally`).

Mesh-native execution: every entry point here is a PURE LOCAL launch.
Inside ``shard_map`` the optimizer runs the same kernels on per-shard
panels — (m, n_loc) column slices or (m_loc, n) row slices — and every
cross-device interaction is a named CollectiveRound of the leaf's
StepProgram, executed by :class:`repro.core.program.Exec` (the psums /
reduce-scatters / all-gathers that used to be plumbed through
``axis_name`` kwargs here).  Tile-alignment is judged against the LOCAL
panel dims: shards whose m_loc doesn't tile fall back to the
reference per shard, exactly like odd shapes on one device.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import grassmann, ref

Array = jax.Array


def _mode() -> str:
    """'compiled' | 'interpret' | 'ref'."""
    if jax.default_backend() == "tpu":
        return "compiled"
    if os.environ.get("REPRO_FORCE_KERNELS") == "1":
        return "interpret"
    return "ref"


def _tiles_ok(dim: int, block: int) -> bool:
    """Whether a summed-over extent splits into whole blocks (a dim
    smaller than the block is one whole block)."""
    return dim % min(block, dim) == 0


KERNEL, COMPOSITE, REFERENCE = "kernel", "composite", "reference"

# (entry point, path, operand shapes) -> number of traces, compiled mode only
_TALLY: dict[tuple[str, str, tuple], int] = {}


def _route(entry: str, path: str, *operands) -> None:
    """Record a compiled-mode dispatch; print the first trace of each
    fallback.  Runs at trace time, so the counts are traces (one per
    compiled program that contains the call), not executions."""
    if _mode() != "compiled":
        return
    key = (entry, path, tuple(tuple(x.shape) for x in operands))
    if path != KERNEL and key not in _TALLY:
        print(f"[kernels] {entry} {list(key[2])}: {path} path", flush=True)
    _TALLY[key] = _TALLY.get(key, 0) + 1


def dispatch_tally() -> dict[tuple[str, str, tuple], int]:
    """Compiled-mode dispatches so far, keyed (entry, path, shapes)."""
    return dict(_TALLY)


def reset_dispatch_tally() -> None:
    _TALLY.clear()


def project(S: Array, G: Array) -> Array:
    """A = S^T G (Eq. 2-3) -> (r, n) fp32.  Kernel: grassmann.project;
    oracle/fallback: ref.project_ref."""
    mode = _mode()
    m, r = S.shape
    n = G.shape[1]
    ok = _tiles_ok(m, grassmann.BM)
    _route("project", KERNEL if ok else REFERENCE, S, G)
    if mode == "ref" or not ok:
        return ref.project_ref(S, G)
    return grassmann.project(S, G, interpret=(mode == "interpret"))


def backproject(S: Array, X: Array) -> Array:
    """Ghat = S X (Eq. 10) -> (m, n) fp32.  Kernel: grassmann.backproject;
    oracle/fallback: ref.backproject_ref."""
    mode = _mode()
    m, r = S.shape
    n = X.shape[1]
    ok = _tiles_ok(m, grassmann.BM)
    _route("backproject", KERNEL if ok else REFERENCE, S, X)
    if mode == "ref" or not ok:
        return ref.backproject_ref(S, X)
    return grassmann.backproject(S, X, interpret=(mode == "interpret"))


def recovery(S: Array, G: Array, Gt: Array, phi: Array) -> Array:
    """Lam = (G - S Gt) * phi (Eq. 10-11) -> (m, n) fp32.  Kernel:
    grassmann.recovery; oracle/fallback: ref.recovery_ref."""
    mode = _mode()
    m, n = G.shape
    ok = _tiles_ok(m, grassmann.BM)
    _route("recovery", KERNEL if ok else REFERENCE, S, G)
    if mode == "ref" or not ok:
        return ref.recovery_ref(G, S, Gt, phi)
    return grassmann.recovery(G, S, Gt, phi, interpret=(mode == "interpret"))


def tangent(G: Array, A: Array, S: Array) -> Array:
    """Grassmann tangent T = -2 G A^T + 2 S (A A^T) (Eq. 4) -> (m, r)
    fp32.  Kernel: grassmann.tangent; oracle/fallback: ref.tangent_ref."""
    mode = _mode()
    m, n = G.shape
    ok = _tiles_ok(m, grassmann.BM)
    _route("tangent", KERNEL if ok else REFERENCE, G, S)
    if mode == "ref" or not ok:
        return ref.tangent_ref(G, A, S)
    return grassmann.tangent(G, A, S, interpret=(mode == "interpret"))


def adam_lowrank(Gt: Array, M: Array, V: Array, step: Array, *,
                 beta1: float = 0.9, beta2: float = 0.999,
                 eps: float = 1e-8, bias_correction: bool = True):
    mode = _mode()
    r, n = Gt.shape
    ok = _tiles_ok(r, 128)
    _route("adam_lowrank", KERNEL if ok else REFERENCE, Gt)
    if mode == "ref" or not ok:
        return ref.adam_lowrank_ref(Gt, M, V, step, beta1, beta2, eps,
                                    bias_correction)
    return grassmann.adam_lowrank(Gt, M, V, step, beta1=beta1, beta2=beta2,
                                  eps=eps, bias_correction=bias_correction,
                                  interpret=(mode == "interpret"))


# --- fused hot-path entry points (single-pass update pipeline) -------------


def project_colnorms(S: Array, G: Array) -> tuple[Array, Array]:
    mode = _mode()
    m, r = S.shape
    n = G.shape[1]
    ok = _tiles_ok(m, grassmann.BM)
    _route("project_colnorms", KERNEL if ok else REFERENCE, S, G)
    if mode == "ref" or not ok:
        return ref.project_colnorms_ref(S, G)
    return grassmann.project_colnorms(S, G, interpret=(mode == "interpret"))


def project_tangent_colnorms(S: Array, G: Array
                             ) -> tuple[Array, Array, Array]:
    """Tracking-step front end: (A = S^T G, ||G_:,j||^2, Grassmann tangent T)
    from one pass over G when the full-m panels fit VMEM
    (m <= grassmann.MAX_FUSED_TANGENT_M), two passes otherwise.

    Inside ``shard_map`` with G column-sharded and S replicated, the same
    local launch runs on each shard's (m, n_loc) panel unchanged and the
    program's ``tangent_psum`` round psums the shard-local tangents into
    the global one — valid because the tangent is linear in the
    cross-shard accumulator W = G A^T (T = -2 W + 2 S (S^T W), and
    A A^T = S^T W since A = S^T G).  A and the column norms stay
    shard-local.
    """
    mode = _mode()
    m, r = S.shape
    n = G.shape[1]
    ok = _tiles_ok(m, grassmann.BM)
    fused = m <= grassmann.MAX_FUSED_TANGENT_M
    _route("project_tangent_colnorms",
           REFERENCE if not ok else KERNEL if fused else COMPOSITE, S, G)
    if mode == "ref" or not ok:
        return ref.project_tangent_colnorms_ref(S, G)
    if fused:
        return grassmann.project_tangent_colnorms(
            S, G, interpret=(mode == "interpret"))
    interp = mode == "interpret"
    A, gsq = grassmann.project_colnorms(S, G, interpret=interp)
    T = grassmann.tangent(G, A, S, interpret=interp)
    return A, gsq, T


def grad_tap(x: Array, dy: Array, s: Array
             ) -> tuple[Array, Array, Array]:
    """Grad-fused backward epilogue (dW = x^T dy, A = S^T dW, per-column
    ||dW||^2) — one launch when the full-b panels fit VMEM
    (b <= grassmann.MAX_GRAD_TAP_B), else the dW matmul followed by the
    single-read :func:`project_colnorms` composite.  Kernel:
    grassmann.grad_tap; oracle/fallback: ref.grad_tap_ref.

    Column-separable in n: inside ``shard_map`` with dy (hence dW)
    column-sharded and S replicated, the local launch's A/norms are
    exactly the global statistics' column slice — no collective needed
    beyond what the leaf's StepProgram already declares.
    """
    mode = _mode()
    b, m = x.shape
    n = dy.shape[1]
    ok = _tiles_ok(m, grassmann.BM)
    fused = ok and b <= grassmann.MAX_GRAD_TAP_B
    _route("grad_tap", KERNEL if fused else COMPOSITE if ok else REFERENCE,
           x, dy, s)
    if mode == "ref":
        return ref.grad_tap_ref(x, dy, s)
    if not fused:
        dw = jnp.dot(x.astype(jnp.float32).T, dy.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        if not ok:
            A, gsq = ref.project_colnorms_ref(s, dw)
        else:
            A, gsq = grassmann.project_colnorms(
                s, dw, interpret=(mode == "interpret"))
        return dw, A, gsq
    return grassmann.grad_tap(x, dy, s, interpret=(mode == "interpret"))


def tangent_gram(S: Array, T: Array, G: Array
                 ) -> tuple[Array, Array, Array, Array]:
    """(T^T G, S^T T, T^T T, S^T S) in one pass over G — the row-family
    tracking step's second-round sufficient statistics.  Kernel:
    grassmann.tangent_gram; oracle/fallback: ref.tangent_gram_ref.

    Inside ``shard_map`` with S, T, G row-sharded, the four outputs are
    psum'd TOGETHER as the program's fused (r, n + 3r) ``gram_psum``
    round — every entry is linear in per-row contributions, so the sum
    is the exact global statistic (the Gram is quadratic in the psum'd
    A, so it provably cannot fold into the first linear round)."""
    mode = _mode()
    m, r = S.shape
    n = G.shape[1]
    ok = _tiles_ok(m, grassmann.BM)
    _route("tangent_gram", KERNEL if ok else REFERENCE, S, G)
    if mode == "ref" or not ok:
        return ref.tangent_gram_ref(S, T, G)
    return grassmann.tangent_gram(S, T, G, interpret=(mode == "interpret"))


def adam_lowrank_norms(Gt: Array, M: Array, V: Array, step: Array, *,
                       beta1: float = 0.9, beta2: float = 0.999,
                       eps: float = 1e-8, bias_correction: bool = True):
    mode = _mode()
    r, n = Gt.shape
    ok = _tiles_ok(r, 128)
    _route("adam_lowrank_norms", KERNEL if ok else REFERENCE, Gt)
    if mode == "ref" or not ok:
        return ref.adam_lowrank_norms_ref(Gt, M, V, step, beta1, beta2, eps,
                                          bias_correction)
    return grassmann.adam_lowrank_norms(
        Gt, M, V, step, beta1=beta1, beta2=beta2, eps=eps,
        bias_correction=bias_correction, interpret=(mode == "interpret"))


def fused_update(G: Array | None, S: Array, Gt: Array | None, Gto: Array,
                 phi: Array | None, coef: Array, clip: Array, *,
                 out_dtype=None, param: Array | None = None,
                 wd_coef: Array | None = None) -> Array:
    mode = _mode()
    m, r = S.shape
    n = Gto.shape[1]
    ok = _tiles_ok(m, grassmann.BM)
    _route("fused_update", KERNEL if ok else REFERENCE, S, Gto)
    if mode == "ref" or not ok:
        return ref.fused_update_ref(G, S, Gt, Gto, phi, coef, clip,
                                    out_dtype=out_dtype, param=param,
                                    wd_coef=wd_coef)
    return grassmann.fused_update(G, S, Gt, Gto, phi, coef, clip,
                                  out_dtype=out_dtype, param=param,
                                  wd_coef=wd_coef,
                                  interpret=(mode == "interpret"))


# --- serving: paged-attention decode --------------------------------------


def paged_attention(q: Array, k_pool: Array, v_pool: Array,
                    block_tables: Array, lengths: Array) -> Array:
    """Block-table decode attention -> (B, Hq, hd).  Kernel:
    paged_attention.paged_attention; oracle/fallback:
    ref.paged_attention_ref.

    Compiled-path gate: hd % 128 == 0 (MXU lane alignment) and
    block_size % 8 == 0 (sublane tiling of the gathered K/V block);
    anything else — including every smoke config — runs the oracle, or
    the kernel in interpret mode when REPRO_FORCE_KERNELS=1 so CI
    exercises the real schedule on any shape.
    """
    from repro.kernels import paged_attention as paged

    mode = _mode()
    hd = q.shape[-1]
    bs = k_pool.shape[1]
    ok = not (hd % 128 or bs % 8)
    _route("paged_attention", KERNEL if ok else REFERENCE, q, k_pool)
    if mode == "ref" or (mode == "compiled" and not ok):
        return ref.paged_attention_ref(q, k_pool, v_pool, block_tables,
                                       lengths)
    return paged.paged_attention(q, k_pool, v_pool, block_tables, lengths,
                                 interpret=(mode == "interpret"))
