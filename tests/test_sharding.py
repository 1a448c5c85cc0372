"""Sharding-rule unit tests against an AbstractMesh of the production
shape (no placeholder devices needed — these are pure spec functions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core.api import get_optimizer
from repro.distributed import sharding as sh
from repro.distributed.context import MeshContext


@pytest.fixture(scope="module")
def ctx():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    return MeshContext(mesh=mesh, batch_axes=("data",))


def _sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


class TestParamRules:
    def test_embed_vocab_parallel(self, ctx):
        spec = sh.spec_for_path("embed", (256000, 4608), ctx)
        assert spec == P("model", "data")

    def test_lm_head(self, ctx):
        assert sh.spec_for_path("lm_head", (4608, 256000), ctx) == \
            P("data", "model")

    def test_column_and_row_parallel(self, ctx):
        assert sh.spec_for_path("layers/attn/wq", (46, 4608, 4096), ctx) == \
            P(None, "data", "model")
        assert sh.spec_for_path("layers/attn/wo", (46, 4096, 4608), ctx) == \
            P(None, "model", "data")
        assert sh.spec_for_path("layers/mlp/w_down", (46, 36864, 4608),
                                ctx) == P(None, "model", "data")

    def test_moe_bank_physical_layout(self, ctx):
        # (L, tp, E_loc, d, f_loc)
        assert sh.spec_for_path("layers/mlp/wg", (56, 16, 1, 6144, 8192),
                                ctx) == P(None, "model", None, "data", None)
        assert sh.spec_for_path("layers/mlp/wd", (56, 16, 1, 8192, 6144),
                                ctx) == P(None, "model", None, None, "data")

    def test_divisibility_guard_drops_axis(self, ctx):
        # 20 heads * 128 = 2560 cols divisible; but a 37-dim can't shard
        spec = sh.spec_for_path("layers/attn/wq", (40, 37, 2560), ctx)
        assert spec == P(None, None, "model")

    def test_scalars_and_vectors_replicated(self, ctx):
        assert sh.spec_for_path("layers/ln1", (46, 4608), ctx) is not None
        assert sh.spec_for_path("final_norm", (4608,), ctx) == P()


class TestOptStateRules:
    def test_states_fully_sharded(self, ctx):
        """M/V are the big fp32 states — every one must be sharded on at
        least one mesh axis (the 13 GB/device regression this guards)."""
        params = {
            "embed": _sds(256000, 4608),
            "layers": {"attn": {"wq": _sds(46, 4608, 4096)},
                       "mlp": {"wg": _sds(56, 16, 1, 6144, 8192)}},
        }
        opt = get_optimizer("subtrack", rank=512)
        specs = sh.opt_state_specs(params, ctx, opt)
        mv_specs = [specs.inner["embed"].M,
                    specs.inner["layers"]["attn"]["wq"].M,
                    specs.inner["layers"]["mlp"]["wg"].M]
        for spec in mv_specs:
            axes = [a for a in spec if a is not None]
            assert axes, f"M/V replicated: {spec}"

    def test_s_follows_m_dim(self, ctx):
        params = {"embed": _sds(256000, 4608)}
        opt = get_optimizer("subtrack", rank=512)
        specs = sh.opt_state_specs(params, ctx, opt)
        # embed (V, d): m = d (transposed canonical) -> S (d, r) shards like d
        assert specs.inner["embed"].S[0] == "data"

    def test_dense_fallback_matches_weight(self, ctx):
        params = {"final_norm": _sds(4608, dtype=jnp.float32)}
        opt = get_optimizer("subtrack", rank=512)
        specs = sh.opt_state_specs(params, ctx, opt)
        assert specs.inner["final_norm"].M == P()


class TestBatchCacheRules:
    def test_batch_sharded_on_dp(self, ctx):
        specs = sh.batch_specs({"tokens": _sds(256, 4096, dtype=jnp.int32)},
                               ctx)
        assert specs["tokens"] == P(("data",), None)

    def test_cache_seq_sharding_when_batch_unshardable(self, ctx):
        # long_500k: batch 1 -> the 524288-seq axis spreads over both axes
        cache = {"k": _sds(56, 1, 524288, 8, 128)}
        specs = sh.cache_specs(cache, ctx, global_batch=1)
        assert specs["k"][2] == ("data", "model")

    def test_cache_batch_sharding_when_divisible(self, ctx):
        cache = {"k": _sds(40, 128, 32768, 8, 128)}
        specs = sh.cache_specs(cache, ctx, global_batch=128)
        assert specs["k"][1] in ("data", ("data",))  # P normalizes 1-tuples
        assert specs["k"][2] == "model"


class TestHotpathSpecs:
    """Column-sharded layout builder for the mesh-native fused hot path."""

    def test_lowrank_leaves_column_sharded(self, ctx):
        params = {"w": _sds(512, 4096), "wt": _sds(4096, 512),
                  "layers": _sds(4, 512, 4096), "b": _sds(4096)}
        specs = sh.hotpath_param_specs(params, ctx, rank=128)
        # canonical n (the wide dim) shards on `model`; m stays replicated
        assert specs["w"] == P(None, "model")
        # transposed leaf: canonical n is the ORIGINAL row dim
        assert specs["wt"] == P("model", None)
        # stack dims replicate — the shard_map'd path requires it
        assert specs["layers"] == P(None, None, "model")
        # dense leaves replicate
        assert specs["b"] == P()

    def test_indivisible_dims_replicate(self, ctx):
        # 1000 divides neither mesh axis (16) -> fully replicated leaf
        specs = sh.hotpath_param_specs({"w": _sds(512, 1000)}, ctx, rank=128)
        assert specs["w"] == P(None, None)

    def test_regime_gate_blocks_undersized_columns(self, ctx):
        # n/g = 4096/16 = 256 < 2r = 1024: column-sharding stops paying
        # (the traffic model's documented rule) -> leaf stays replicated
        specs = sh.hotpath_param_specs({"w": _sds(2048, 4096)}, ctx,
                                       rank=512)
        assert specs["w"] == P(None, None)
        # at rank 128 the same leaf is comfortably inside the regime
        specs = sh.hotpath_param_specs({"w": _sds(2048, 4096)}, ctx,
                                       rank=128)
        assert specs["w"] == P(None, "model")

    def test_specs_feed_column_shardable_plans(self, ctx):
        from repro.core import plan as plan_lib
        params = {"w": _sds(512, 4096)}
        specs = sh.hotpath_param_specs(params, ctx, rank=128)
        plans = plan_lib.make_plans(params, 128, specs=specs)
        assert plan_lib.spec_column_axes(plans["w"]) == ("model",)


class TestHotpathRegimeSelection:
    """Regime-aware layout builder: column vs row per leaf, by the
    modeled per-device bytes (repro.kernels.traffic)."""

    def test_column_preferred_when_both_admissible(self, ctx):
        # square leaf, both gates pass at rank 128 — the byte model
        # prefers column (state shards with the columns; scalar psum)
        specs = sh.hotpath_param_specs({"w": _sds(4096, 4096)}, ctx,
                                       rank=128)
        assert specs["w"] == P(None, "model")

    def test_row_leaf_picks_row_regime(self, ctx):
        # n = 4097 divides neither axis -> column inadmissible; m = 2048
        # with m/16 = 128 >= 2r = 128 -> the leaf row-shards instead of
        # replicating (the wo/w_down coverage gap this PR closes)
        specs = sh.hotpath_param_specs({"w": _sds(2048, 4097)}, ctx,
                                       rank=64)
        assert specs["w"] == P("model", None)
        # transposed twin: canonical m is the ORIGINAL column dim
        specs = sh.hotpath_param_specs({"w": _sds(4097, 2048)}, ctx,
                                       rank=64)
        assert specs["w"] == P(None, "model")

    def test_row_gate_boundary_at_two_r(self, ctx):
        # m/g = 4096/16 = 256: admissible at r = 128 (== 2r), blocked at
        # r = 129 — the m/g >= 2r rule, mirroring the column gate
        specs = sh.hotpath_param_specs({"w": _sds(4096, 4097)}, ctx,
                                       rank=128)
        assert specs["w"] == P("model", None)
        specs = sh.hotpath_param_specs({"w": _sds(4096, 4097)}, ctx,
                                       rank=129)
        assert specs["w"] == P(None, None)

    def test_regimes_restriction(self, ctx):
        # the trainer's --hotpath-layout flag: restricting to one regime
        # replicates leaves only the other regime could shard
        params = {"w": _sds(2048, 4097)}
        specs = sh.hotpath_param_specs(params, ctx, rank=64,
                                       regimes=("column",))
        assert specs["w"] == P(None, None)
        specs = sh.hotpath_param_specs(params, ctx, rank=64,
                                       regimes=("row",))
        assert specs["w"] == P("model", None)

    def test_row_state_threads_into_row_ranking(self, ctx):
        """The layout builder ranks the row family by the STATE FLAVOUR
        the optimizer will actually run (row_state mirrors
        LowRankConfig.row_state): with "replicated" the rs byte
        advantage must not leak into the column-vs-row comparison."""
        from repro.distributed.sharding import _row_bytes
        from repro.kernels import traffic
        m, n, r, g = 2048, 4096, 64, 16
        rep = traffic.sharded_row_fused_step_bytes(m, n, r, g).total
        rs = traffic.sharded_row_rs_fused_step_bytes(m, n, r, g).total
        assert rs < rep
        assert _row_bytes(m, n, r, g, ("row",), "auto") == rs
        assert _row_bytes(m, n, r, g, ("row",), "replicated") == rep
        assert _row_bytes(m, n, r, g, ("row-rs",), "auto") == rs
        # forced rs on an indivisible n degrades to the replicated
        # flavour, exactly like program._row_flavor
        assert _row_bytes(m, n + 1, r, g, ("row",), "reduce-scatter") == \
            traffic.sharded_row_fused_step_bytes(m, n + 1, r, g).total
        # restricting to row-rs alone replicates inadmissible leaves
        assert _row_bytes(m, n + 1, r, g, ("row-rs",), "auto") is None

    def test_row_specs_feed_row_shardable_plans(self, ctx):
        from repro.core import plan as plan_lib
        params = {"w": _sds(2048, 4097)}
        specs = sh.hotpath_param_specs(params, ctx, rank=64)
        plans = plan_lib.make_plans(params, 64, specs=specs)
        assert plan_lib.spec_row_axes(plans["w"]) == ("model",)
        assert plan_lib.spec_regime(plans["w"]) == "row"


class TestHloAnalysis:
    def test_scan_trip_multiplication(self):
        """Validated against a real compiled program: the analyzer must
        multiply while-body FLOPs by the known trip count (cost_analysis
        famously does not — the reason this module exists)."""
        from repro.distributed.hlo_analysis import analyze_hlo

        def f(x):
            def body(c, _):
                return c @ c, None
            c, _ = jax.lax.scan(body, x, None, length=10)
            return c

        comp = jax.jit(f).lower(jnp.zeros((64, 64), jnp.float32)).compile()
        s = analyze_hlo(comp.as_text(), 1)
        expected = 10 * 2 * 64 ** 3
        assert abs(s.flops - expected) / expected < 0.01

    def test_collective_formulas_on_synthetic_hlo(self):
        from repro.distributed.hlo_analysis import analyze_hlo
        hlo = """
ENTRY %main (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128]{1,0} parameter(0)
  %ag = f32[128,128]{1,0} all-gather(%p0), channel_id=1, replica_groups=[2,8]<=[16], dimensions={0}
  %ar = f32[128,128]{1,0} all-reduce(%ag), channel_id=2, replica_groups=[1,16]<=[16], to_apply=%add
  ROOT %cp = f32[128,128]{1,0} collective-permute(%ar), channel_id=3, source_target_pairs={{0,1}}
}
"""
        s = analyze_hlo(hlo, 16)
        B = 128 * 128 * 4
        assert abs(s.collective_by_kind["all-gather"] - B * 7 / 8) < 1
        assert abs(s.collective_by_kind["all-reduce"] - 2 * B * 15 / 16) < 1
        assert abs(s.collective_by_kind["collective-permute"] - B) < 1
