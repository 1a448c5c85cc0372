"""Main-path Pallas kernels compiled for a described (not attached) TPU v5e
at the widths the chip runs them: the 1B ladder model's attention, MLP
(d_ff = 5461, a ragged column extent) and head leaves at rank 512, the
grad-fused tap at batch 8 x seq 256 tokens, the row-regime tracking panel
of a four-chip mesh, and qwen1.5-4b's paged decode.  The TPU compiler
refuses here what interpret mode cannot show: misaligned blocks, scoped
VMEM overruns, compiler faults.  Nothing runs; no result is checked.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import grassmann, paged_attention

R = 512                                  # llama-1b's paper rank
LEAVES = [(2048, 2048), (2048, 5461), (2048, 32000)]   # attn, MLP, head
TOKENS = 8 * 256


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
