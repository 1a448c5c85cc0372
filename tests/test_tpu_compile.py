"""Main-path Pallas kernels compiled for a described (not attached) TPU v5e
at the widths the chip runs them: the 1B ladder model's attention, MLP
(d_ff = 5461, a ragged column extent) and head leaves at rank 512, the
grad-fused tap at batch 8 x seq 256 tokens, the row-regime tracking panel
of a four-chip mesh, qwen1.5-4b's paged decode, and the training cells'
flash attention, forward and backward.  The TPU compiler
refuses here what interpret mode cannot show: misaligned blocks, scoped
VMEM overruns, compiler faults.  Nothing runs; no result is checked.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention, grassmann, paged_attention

R = 512                                  # llama-1b's paper rank
LEAVES = [(2048, 2048), (2048, 5461), (2048, 32000)]   # attn, MLP, head
TOKENS = 8 * 256


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compile_for(one_chip):
    """compile_for(fn, *shapes) -> compiled text, with the persistent
    compilation cache off (an entry compiled for a described chip cannot
    be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("m,n", LEAVES)
def test_project_colnorms(compile_for, m, n):
    compile_for(grassmann.project_colnorms, ((m, R), F32), ((m, n), BF16))


@pytest.mark.parametrize("m,n", LEAVES)
def test_adam_lowrank_norms(compile_for, m, n):
    compile_for(lambda g, mm, v, t: grassmann.adam_lowrank_norms(g, mm, v, t),
                ((R, n), F32), ((R, n), F32), ((R, n), F32), ((), I32))


@pytest.mark.parametrize("m,n", LEAVES)
def test_fused_update(compile_for, m, n):
    def upd(G, S, Gt, Gto, phi, coef, clip, p, wd):
        return grassmann.fused_update(G, S, Gt, Gto, phi, coef, clip,
                                      out_dtype=BF16, param=p, wd_coef=wd)

    compile_for(upd, ((m, n), BF16), ((m, R), F32), ((R, n), F32),
                ((R, n), F32), ((n,), F32), ((), F32), ((), F32),
                ((m, n), BF16), ((), F32))


@pytest.mark.parametrize("n", [2048, 5461])
def test_project_tangent_colnorms(compile_for, n):
    m = grassmann.MAX_FUSED_TANGENT_M      # the largest single-launch panel
    compile_for(grassmann.project_tangent_colnorms, ((m, R), F32),
                ((m, n), BF16))


@pytest.mark.parametrize("m,n", LEAVES[:2])
def test_grad_tap(compile_for, m, n):
    assert TOKENS <= grassmann.MAX_GRAD_TAP_B
    compile_for(grassmann.grad_tap, ((TOKENS, m), BF16),
                ((TOKENS, n), BF16), ((m, R), F32))


@pytest.mark.parametrize("n", [2048, 5461])
def test_tangent_gram_four_chip_row_panel(compile_for, n):
    m_loc = 2048 // 4                     # row regime on a (1, 4) mesh
    compile_for(grassmann.tangent_gram, ((m_loc, R), F32),
                ((m_loc, R), F32), ((m_loc, n), BF16))


def test_tangent_head(compile_for):
    compile_for(grassmann.tangent, ((2048, 32000), BF16), ((R, 32000), F32),
                ((2048, R), F32))


def test_paged_attention_qwen_decode(compile_for):
    B, H, hd, bs = 8, 20, 128, 16           # qwen1.5-4b, 8 live requests
    W = (64 + 16 + bs - 1) // bs            # prompt 64 + 16 new tokens
    nb = 1 + B * W
    compile_for(paged_attention.paged_attention, ((B, H, hd), BF16),
                ((nb, bs, H, hd), BF16), ((nb, bs, H, hd), BF16),
                ((B, W), I32), ((B,), I32))


@pytest.mark.parametrize("H,hd,vd", [(20, 128, 128),     # qwen1.5-4b
                                     (40, 96, 64)])      # minicpm3-4b MLA
@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_flash_attention_train_cells(compile_for, H, hd, vd, pass_):
    """Under ``highest`` default matmul precision, as the benchmark's warm
    start runs the model: the kernels' bf16 products must not ask Mosaic
    for a float32 contraction."""
    S = 2048
    shapes = [((1, S, H, d), BF16) for d in (hd, hd, vd)]

    def grads(q, k, v, do):
        return jax.vjp(flash_attention.flash_attention, q, k, v)[1](do)

    with jax.default_matmul_precision("highest"):
        if pass_ == "forward":
            text = compile_for(flash_attention.flash_attention, *shapes)
        else:
            text = compile_for(grads, *shapes, ((1, S, H, vd), BF16))
    names = ("flash_fwd",) if pass_ == "forward" else ("flash_dq", "flash_dkv")
    assert all(name in text for name in names)


@pytest.mark.parametrize("H,Hkv", [(20, 20), (20, 2)])
def test_flash_attention_on_a_four_chip_mesh(compile_for, topo, monkeypatch,
                                             H, Hkv):
    """On a mesh the training attention runs the kernel per shard (GSPMD
    cannot partition it): heads over the model axis where both head
    counts divide it, else replicated."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed import context
    from repro.kernels import ops
    from repro.models.attention import self_attention

    monkeypatch.setattr(ops, "_mode", lambda: "compiled")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    ctx = context.MeshContext(mesh=mesh)
    monkeypatch.setattr(context, "_CURRENT", ctx)

    def arg(h, d=128):
        spec = P(None, None, "model" if h % 4 == 0 else None, None)
        return jax.ShapeDtypeStruct((1, 2048, h, d), BF16,
                                    sharding=NamedSharding(mesh, spec))

    def grads(q, k, v, do):
        return jax.vjp(self_attention, q, k, v)[1](do)

    text = jax.jit(grads).lower(arg(H), arg(Hkv), arg(Hkv), arg(H)
                                ).compile().as_text()
    assert "flash_dq" in text and "flash_dkv" in text
