"""Golden-program tests for the StepProgram IR (repro.core.program).

The program is the single source of truth for the optimizer hot path's
collective structure: the runtime executor fires exactly its declared
rounds, the traffic byte model charges exactly their wire bytes, and
tests/test_mesh_fused.py pins compiled HLO against
``StepProgram.collective_counts``.  These tests pin the PROGRAM itself —
round names, kinds, payload shapes and the golden per-regime count
dicts — so none of the three consumers can drift without a test telling
the story.  No mesh devices are needed: programs are static data
(AbstractMesh suffices)."""

import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core import plan as plan_lib
from repro.core import program as program_lib
from repro.core.program import (ALL_GATHER, ALL_REDUCE, GRAD_FUSED,
                                REDUCE_SCATTER, CollectiveRound,
                                build_program, regime_rounds)
from repro.core.subtrack import LowRankConfig
from repro.kernels import traffic

M, N, RANK, G = 64, 256, 16, 8

MESH = AbstractMesh((G,), ("x",))
CFG = LowRankConfig(rank=RANK, use_kernels=True)

COL = plan_lib.plan_for_shape((M, N), RANK, spec=P(None, "x"))
ROW = plan_lib.plan_for_shape((M, N), RANK, spec=P("x", None))
ROW_ODD_N = plan_lib.plan_for_shape((M, N + 1), RANK, spec=P("x", None))

# The golden collective-count table — the SAME dicts
# tests/test_mesh_fused.py asserts against compiled HLO (its expectation
# is read off build_program, so equality here welds HLO pin <-> program).
GOLDEN_COUNTS = {
    ("replicated", False): {},
    ("replicated", True): {},
    ("column", False): {"all-reduce": 1},
    ("column", True): {"all-reduce": 2},
    ("row", False): {"all-reduce": 1},
    ("row", True): {"all-reduce": 2},
    ("row-rs", False): {"reduce-scatter": 1, "all-gather": 1},
    ("row-rs", True): {"all-reduce": 2, "all-gather": 1},
}


class TestGoldenRounds:
    def test_column_rounds(self):
        assert regime_rounds("column", M, N, RANK, G, tracking=False) == (
            CollectiveRound("clip", ALL_REDUCE, 1, 1),)
        assert regime_rounds("column", M, N, RANK, G, tracking=True) == (
            CollectiveRound("tangent_psum", ALL_REDUCE, M, RANK),
            CollectiveRound("clip", ALL_REDUCE, 1, 1))
        # non-recovery plain step has NO collective at all
        assert regime_rounds("column", M, N, RANK, G, tracking=False,
                             recovery=False) == ()

    def test_row_rounds(self):
        assert regime_rounds("row", M, N, RANK, G, tracking=False) == (
            CollectiveRound("proj", ALL_REDUCE, RANK + 1, N),)
        assert regime_rounds("row", M, N, RANK, G, tracking=True) == (
            CollectiveRound("proj", ALL_REDUCE, RANK + 1, N),
            CollectiveRound("gram_psum", ALL_REDUCE, RANK, N + 3 * RANK))

    def test_row_rs_rounds(self):
        assert regime_rounds("row-rs", M, N, RANK, G, tracking=False) == (
            CollectiveRound("proj", REDUCE_SCATTER, RANK + 1, N),
            CollectiveRound("epilogue_gather", ALL_GATHER,
                            2 * RANK + 2, N))
        assert regime_rounds("row-rs", M, N, RANK, G, tracking=True) == (
            CollectiveRound("proj", ALL_REDUCE, RANK + 1, N),
            CollectiveRound("gram_psum", ALL_REDUCE, RANK, N + 3 * RANK),
            CollectiveRound("epilogue_gather", ALL_GATHER, RANK + 2, N))
        # without recovery the gather shrinks to the Gto panel alone
        plain_nr = regime_rounds("row-rs", M, N, RANK, G, tracking=False,
                                 recovery=False)
        assert plain_nr[-1] == CollectiveRound("epilogue_gather",
                                               ALL_GATHER, RANK, N)

    def test_replicated_and_group1_empty(self):
        assert regime_rounds("replicated", M, N, RANK, G,
                             tracking=True) == ()
        assert regime_rounds("row-rs", M, N, RANK, 1, tracking=True) == ()

    @pytest.mark.parametrize("regime,tracking", list(GOLDEN_COUNTS))
    def test_golden_collective_counts(self, regime, tracking):
        counts: dict = {}
        for rnd in regime_rounds(regime, M, N, RANK, G, tracking=tracking):
            counts[rnd.kind] = counts.get(rnd.kind, 0) + 1
        assert counts == GOLDEN_COUNTS[(regime, tracking)]


class TestWireBytes:
    def test_ring_formulas(self):
        ar = CollectiveRound("a", ALL_REDUCE, 4, 8)
        rs = CollectiveRound("b", REDUCE_SCATTER, 4, 8)
        ag = CollectiveRound("c", ALL_GATHER, 4, 8)
        payload = 4 * 8 * 4
        assert ar.wire_bytes(8) == int(2 * 7 / 8 * payload)
        # RS moves half an AR's wire; AG charges the gathered panel once
        assert rs.wire_bytes(8) == int(7 / 8 * payload)
        assert ag.wire_bytes(8) == int(7 / 8 * payload)
        for rnd in (ar, rs, ag):
            assert rnd.wire_bytes(1) == 0

    @pytest.mark.parametrize("regime", ["column", "row", "row-rs"])
    @pytest.mark.parametrize("tracking", [False, True])
    def test_traffic_collective_terms_equal_program(self, regime,
                                                    tracking):
        """The byte model's collective term IS the program's wire bytes
        (traffic.program_collective_bytes reads regime_rounds)."""
        want = sum(r.wire_bytes(G)
                   for r in regime_rounds(regime, M, N, RANK, G,
                                          tracking=tracking))
        assert traffic.program_collective_bytes(
            regime, M, N, RANK, G, tracking=tracking) == want


class TestBuildProgram:
    def test_column_program(self):
        prog = build_program(COL, CFG, MESH, tracking=False)
        assert prog.regime == "column" and prog.axes == ("x",)
        assert prog.shards == G
        assert prog.grad_layout == "column"
        assert prog.state_layout == "column"
        assert prog.schedule == "tangent"
        assert prog.collective_counts() == GOLDEN_COUNTS[("column", False)]

    def test_row_flavors(self):
        # auto (default): n % g == 0 and modeled bytes lower -> row-rs
        prog = build_program(ROW, CFG, MESH, tracking=False)
        assert prog.regime == "row-rs"
        assert prog.state_layout == "slice" and prog.schedule == "gram"
        assert prog.collective_counts() == GOLDEN_COUNTS[("row-rs", False)]
        # indivisible n falls back to replicated M/V
        assert build_program(ROW_ODD_N, CFG, MESH,
                             tracking=False).regime == "row"
        # forced flavours
        rep = LowRankConfig(rank=RANK, use_kernels=True,
                            row_state="replicated")
        rs = LowRankConfig(rank=RANK, use_kernels=True,
                           row_state="reduce-scatter")
        assert build_program(ROW, rep, MESH, tracking=False).regime == "row"
        assert build_program(ROW, rs, MESH,
                             tracking=False).regime == "row-rs"
        # forcing rs on an indivisible n still degrades gracefully
        assert build_program(ROW_ODD_N, rs, MESH,
                             tracking=False).regime == "row"

    def test_replicated_fallbacks(self):
        # no mesh / no kernels / spec-less leaves lower replicated
        assert build_program(COL, CFG, None, tracking=False).regime == \
            "replicated"
        no_k = LowRankConfig(rank=RANK, use_kernels=False)
        assert build_program(COL, no_k, MESH, tracking=False).regime == \
            "replicated"
        specless = plan_lib.plan_for_shape((M, N), RANK)
        assert build_program(specless, CFG, MESH,
                             tracking=False).regime == "replicated"
        # non-shardable refresh methods route tracking steps away only
        svd = LowRankConfig(rank=RANK, use_kernels=True, method="svd")
        assert build_program(COL, svd, MESH, tracking=True).regime == \
            "replicated"
        assert build_program(COL, svd, MESH, tracking=False).regime == \
            "column"
        # reorth scrubs route ROW tracking steps away (QR of a
        # row-sharded basis is not shard-local); column keeps them
        scrub = LowRankConfig(rank=RANK, use_kernels=True,
                              reorth_interval=2)
        assert build_program(ROW, scrub, MESH, tracking=True).regime == \
            "replicated"
        assert build_program(COL, scrub, MESH, tracking=True).regime == \
            "column"
        # both trailing dims sharded matches neither regime
        both = plan_lib.plan_for_shape((M, N), RANK, spec=P("x", "y"))
        assert build_program(both, CFG, MESH, tracking=False).regime == \
            "replicated"

    def test_frozen_subspace_tracking_declares_plain_rounds(self):
        """method="none" tracking steps move no basis, so no geodesic
        collective ever fires — the program must declare (and the byte
        model charge, and the HLO pins expect) exactly the PLAIN rounds,
        in every regime."""
        frozen = LowRankConfig(rank=RANK, use_kernels=True, method="none")
        for plan in (COL, ROW):
            tr = build_program(plan, frozen, MESH, tracking=True)
            pl = build_program(plan, frozen, MESH, tracking=False)
            assert tr.rounds == pl.rounds
            assert tr.tracking and not pl.tracking
        assert build_program(ROW, frozen, MESH,
                             tracking=True).collective_counts() == \
            GOLDEN_COUNTS[("row-rs", False)]

    def test_replicated_program_declares_nothing(self):
        prog = build_program(COL, CFG, None, tracking=True)
        assert prog.rounds == () and prog.shards == 1
        assert prog.collective_wire_bytes() == 0


class TestGradFusedRounds:
    """The grad-fused tap is a LOCAL round: it rides in the IR (one
    declaration for the runtime, the byte model and the tools) but
    lowers to no HLO collective, so every golden count — and with it
    the test_mesh_fused HLO weld — is untouched by ``tapped=True``."""

    def test_regime_rounds_tap(self):
        assert regime_rounds("replicated", M, N, RANK, 1, tracking=False,
                             tapped=True) == (
            CollectiveRound("grad_tap", GRAD_FUSED, RANK + 1, N),)
        # tracking steps never tap (the refresh needs full-width G)
        assert regime_rounds("replicated", M, N, RANK, 1, tracking=True,
                             tapped=True) == ()
        # the tap prepends to the column regime's rounds, wire-free
        col = regime_rounds("column", M, N, RANK, G, tracking=False,
                            tapped=True)
        assert col[0].name == "grad_tap" and col[0].wire_bytes(G) == 0
        assert col[1:] == regime_rounds("column", M, N, RANK, G,
                                        tracking=False)

    def test_tapped_replicated_program(self):
        prog = build_program(COL, CFG, None, tracking=False, tapped=True)
        assert prog.regime == "replicated"
        rnd = prog.round("grad_tap")
        assert rnd == CollectiveRound("grad_tap", GRAD_FUSED, RANK + 1, N)
        assert prog.collective_counts() == \
            GOLDEN_COUNTS[("replicated", False)]
        assert prog.collective_wire_bytes() == 0
        # the tapped program carries a round, so it gets a real executor
        # (for Exec.has gates) — but collective() on a local round is
        # still the identity
        ex = program_lib.executor(prog)
        assert ex.has("grad_tap")
        x = jnp.ones((3, 4))
        assert ex.collective("grad_tap", x) is x

    def test_tapped_column_program_keeps_golden_counts(self):
        prog = build_program(COL, CFG, MESH, tracking=False, tapped=True)
        assert prog.regime == "column"
        assert prog.round("grad_tap") is not None
        assert prog.collective_counts() == GOLDEN_COUNTS[("column", False)]

    def test_tap_dropped_where_unsupported(self):
        # tracking steps and the row regimes (the stacked psum IS the
        # projection — a pre-projected tap cannot ride it) drop the tap
        assert build_program(COL, CFG, MESH, tracking=True,
                             tapped=True).round("grad_tap") is None
        for plan in (ROW, ROW_ODD_N):
            prog = build_program(plan, CFG, MESH, tracking=False,
                                 tapped=True)
            assert prog.regime in ("row", "row-rs")
            assert prog.round("grad_tap") is None


GRASS_CFG = LowRankConfig(rank=RANK, use_kernels=True, method="grass")


class TestGrassProgram:
    """Grass (arXiv:2406.17660) as the fifth regime: S is a one-hot row
    selection, so the projection is a gather — declared as the local
    ``sel_gather`` round, never shard_map'd."""

    def test_grass_regime_and_rounds(self):
        specless = plan_lib.plan_for_shape((M, N), RANK)
        prog = build_program(specless, GRASS_CFG, None, tracking=False)
        assert prog.regime == "grass"
        assert prog.round("sel_gather") == \
            CollectiveRound("sel_gather", GRAD_FUSED, RANK, N)
        assert prog.collective_counts() == {}
        assert prog.collective_wire_bytes() == 0

    def test_grass_never_shard_maps(self):
        # even a column-shardable leaf on a live mesh stays grass with
        # no shard_map axes (the top-r selection contracts over all
        # columns, like the SVD refresh)
        for tracking in (False, True):
            prog = build_program(COL, GRASS_CFG, MESH, tracking=tracking)
            assert prog.regime == "grass"
            assert prog.axes == () and prog.shards == 1

    def test_grass_tap_subsumes_gather(self):
        # the tap panel IS the gathered rows + norms: a tapped grass
        # program carries grad_tap and drops sel_gather
        specless = plan_lib.plan_for_shape((M, N), RANK)
        prog = build_program(specless, GRASS_CFG, None, tracking=False,
                             tapped=True)
        assert prog.round("grad_tap") is not None
        assert prog.round("sel_gather") is None
        # tracking keeps the gather (refresh re-selects from full G)
        tr = build_program(specless, GRASS_CFG, None, tracking=True,
                           tapped=True)
        assert tr.round("sel_gather") is not None
        assert tr.round("grad_tap") is None

    def test_grass_tracks(self):
        prog = build_program(COL, GRASS_CFG, MESH, tracking=True)
        assert prog.tracks  # grass refreshes move the selection


class TestExec:
    def test_null_exec_identities(self):
        x = jnp.ones((3, 4))
        ex = program_lib.NULL_EXEC
        assert ex.schedule == "tangent"
        assert not ex.has("proj") and not ex.has("clip")
        assert ex.collective("proj", x) is x
        assert ex.psum(x) is x
        assert ex.state_slice(x) is x
        assert not ex.rows_sharded

    def test_executor_falls_back_to_null(self):
        prog = build_program(COL, CFG, None, tracking=False)
        assert program_lib.executor(prog) is program_lib.NULL_EXEC

    def test_exec_program_reads(self):
        prog = build_program(ROW, CFG, MESH, tracking=False)  # row-rs
        ex = program_lib.Exec(prog)
        assert ex.schedule == "gram" and ex.rows_sharded
        assert ex.has("proj") and ex.has("epilogue_gather")
        assert not ex.has("clip")
        assert ex.state_width(N) == N // G
        col_ex = program_lib.Exec(build_program(COL, CFG, MESH,
                                                tracking=False))
        assert col_ex.state_width(N) == N and col_ex.has("clip")


class TestLowering:
    def test_replicated_lower_is_identity(self):
        prog = build_program(COL, CFG, None, tracking=False)

        def fn(g, st):
            return g, st

        assert program_lib.lower(prog, fn, mesh=None, batch_dims=0,
                                 with_param=False) is fn

    def test_describe_lists_rounds(self):
        prog = build_program(ROW, CFG, MESH, tracking=True)
        text = prog.describe()
        assert "row-rs" in text and "gram" in text
        assert "proj" in text and "gram_psum" in text
        assert "epilogue_gather" in text and "all-gather" in text
