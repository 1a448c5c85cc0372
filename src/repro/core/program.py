"""StepProgram IR: declarative per-bucket execution plans for the optimizer
hot path, and the single lowering path that runs them.

Motivation (PR 5): PRs 1-4 grew three hand-built execution regimes —
replicated, column-sharded and row-sharded — whose dispatch logic was
smeared across ``subtrack.update`` (shard_info_for / axis-name plumbing),
``subspace`` (track_subspace vs track_subspace_rowsharded),
``lowrank_adam`` (per-regime psum placement) and ``distributed/sharding``.
This module makes the per-leaf execution scheme a first-class object:

* :func:`build_program` classifies a :class:`~repro.core.plan.ParamPlan`
  (+ config + mesh) into a :class:`StepProgram` — the regime, the
  shard_map axes, the Adam-state layout, the tracking schedule, and the
  full list of :class:`CollectiveRound`\\ s (name, kind, payload shape)
  the step is allowed to execute;
* :func:`regime_rounds` is the **single source of truth** for the
  collective structure: the byte model in :mod:`repro.kernels.traffic`
  charges wire bytes off these rounds, the HLO pins in
  ``tests/test_mesh_fused.py`` assert compiled collective counts against
  :meth:`StepProgram.collective_counts`, and the runtime
  :class:`Exec`\\ utor will only fire collectives the program declares —
  three consumers, one definition, no drift possible;
* :func:`lower` turns a per-matrix step function into the shard_map'd
  (or plain) runner, deriving every in/out PartitionSpec from the
  program's declared layouts;
* :class:`Exec` is the runtime face of a program inside the lowered
  step: the math code in ``subspace`` / ``lowrank_adam`` expresses its
  schedule once, invoking collectives **by round name**
  (``exec.collective("proj", x)``); rounds the program does not declare
  are identities, so one code path serves all four regimes.

The five regimes
----------------
========== ============ =============== ======================================
regime     G/S layout   M/V layout      collectives (plain / tracking)
========== ============ =============== ======================================
replicated whole leaf   whole leaf      none (single device / GSPMD)
column     n sharded    n sharded       clip scalar AR / + (m, r) tangent AR
row        m sharded    replicated      (r+1, n) proj AR / + (r, n+3r) Gram AR
row-rs     m sharded    n/g slice       (r+1, n) proj RS + epilogue AG /
                                        proj AR + Gram AR + epilogue AG
grass      whole leaf   whole leaf      local ``sel_gather`` round only: S is
                                        a one-hot row selection (Grass,
                                        arXiv:2406.17660), A = S^T G a gather
========== ============ =============== ======================================

Grad-fused plain steps (PR 6) additionally declare a local ``grad_tap``
round in the replicated / column / grass regimes: the (r+1, n)
[A; colnorms] panel is produced by the model's backward-pass epilogue
(``kernels.ops.grad_tap`` via ``models.common.tapped_matmul``) and the
optimizer consumes it instead of re-reading the full-width gradient.
Local rounds are zero-wire and compile to no HLO collective — the pins
and the ring model see straight through them.

``row-rs`` is the reduce-scatter flavour of the row regime (the ROADMAP
item this PR lands): instead of psumming the stacked (r+1, n)
[A; colnorms] panel to every row shard and recomputing the full-width
Adam pass redundantly (replicated M/V — the row regime's memory cost),
the panel is reduce-SCATTERED so each shard owns an n/g column slice of
M/V, the Adam pass runs sharded, and one all-gather of the
[G~; G~^O; phi; clip-partials] panel restores full width right before
``fused_update`` writes the local rows.  Per-device M/V memory drops by
the group factor and the sliced state passes outweigh the extra gather
wire everywhere inside the row gate (see the byte comparison in
``_row_flavor`` and ``traffic.sharded_row_rs_*``).  Tracking steps keep
the row regime's all-reduce front end (the tangent needs global A) and
shard only the rotation + Adam passes, gathering [G~^O; phi; partials]
at the end — exactly 2 collectives plain / 3 tracking.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from repro.core import plan as plan_lib

F32 = 4

REGIMES = ("replicated", "column", "row", "row-rs", "grass")

# collective kinds (HLO opcode names — hlo_analysis counts these)
ALL_REDUCE = "all-reduce"
REDUCE_SCATTER = "reduce-scatter"
ALL_GATHER = "all-gather"
# Local (non-collective) round kind: a declared data-flow edge of the
# step — the backward-pass tap panel a grad-fused step consumes, or the
# Grass row gather — with zero wire bytes and no HLO collective op.  It
# exists in the IR so the traffic model, the executor gates
# (``Exec.has``) and the tools see one declaration, same as the real
# collectives.
GRAD_FUSED = "grad-fused"


@dataclass(frozen=True)
class CollectiveRound:
    """One declared collective of a step program.

    ``rows, cols`` are the logical 2-D payload shape: the pre-collective
    per-device operand for all-reduce / reduce-scatter, the *gathered*
    (output) panel for all-gather — in both conventions this is the HLO
    result-bytes quantity the ring wire model multiplies.
    """

    name: str          # semantic label the runtime invokes it by
    kind: str          # ALL_REDUCE | REDUCE_SCATTER | ALL_GATHER
    rows: int
    cols: int
    dtype_bytes: int = F32

    @property
    def payload_bytes(self) -> int:
        return self.rows * self.cols * self.dtype_bytes

    def wire_bytes(self, group: int) -> int:
        """Per-device ring-model wire bytes (matching
        repro.distributed.hlo_analysis: AR = 2(g-1)/g * result, RS =
        (g-1)/g * result * g with result = payload/g, AG = (g-1)/g *
        gathered result)."""
        if self.kind == GRAD_FUSED or group <= 1:
            return 0
        ring = (group - 1) / group
        if self.kind == ALL_REDUCE:
            return int(2.0 * ring * self.payload_bytes)
        if self.kind in (REDUCE_SCATTER, ALL_GATHER):
            return int(ring * self.payload_bytes)
        raise ValueError(f"unknown collective kind {self.kind!r}")


def regime_rounds(regime: str, m: int, n: int, r: int, group: int, *,
                  tracking: bool, recovery: bool = True,
                  tapped: bool = False
                  ) -> tuple[CollectiveRound, ...]:
    """The collective rounds of one optimizer step — the single source of
    truth consumed by the runtime executor, the traffic byte model and
    the HLO count pins.

    Round names are the contract with the lowered code paths:

    * ``proj``            — makes the stacked (r+1, n) [A; colnorms]
                            projection panel global (row regimes; the
                            projection contracts over sharded rows);
    * ``tangent_psum``    — (m, r) tangent accumulator psum (column
                            tracking; T is linear in W = G A^T);
    * ``gram_psum``       — fused (r, n + 3r) [T^T G | S^T T | T^T T |
                            S^T S] psum (row-family tracking; the Gram
                            is quadratic in ``proj``'s result, so this
                            second round is provably irreducible);
    * ``clip``            — the Eq. 12 scalar psum (column; the row
                            family gets the clip free off replicated or
                            gathered per-column quantities);
    * ``epilogue_gather`` — row-rs only: all-gather of the stacked
                            per-column epilogue panel ([G~; ] G~^O; phi;
                            clip partials) back to full width before
                            ``fused_update``;
    * ``grad_tap``        — grad-fused plain steps (``tapped=True``):
                            the (r+1, n) [A; colnorms] panel emitted by
                            the backward-pass epilogue that replaces the
                            optimizer's own projection read of G.  Local
                            kind, zero wire bytes;
    * ``sel_gather``      — Grass regime: S is a one-hot row selection,
                            so A = S^T G is an (r, n) row gather of G
                            (no MXU projection).  Local kind.
    """
    tap = ((CollectiveRound("grad_tap", GRAD_FUSED, r + 1, n),)
           if tapped and not tracking else ())
    if regime == "grass":
        # the tap subsumes the gather (it IS the gathered rows + norms)
        return tap if tap else (
            CollectiveRound("sel_gather", GRAD_FUSED, r, n),)
    if group <= 1 or regime == "replicated":
        return tap
    if regime == "column":
        rounds = list(tap)
        if tracking:
            rounds.append(CollectiveRound("tangent_psum", ALL_REDUCE, m, r))
        if recovery:
            rounds.append(CollectiveRound("clip", ALL_REDUCE, 1, 1))
        return tuple(rounds)
    if regime == "row":
        rounds = [CollectiveRound("proj", ALL_REDUCE, r + 1, n)]
        if tracking:
            rounds.append(CollectiveRound("gram_psum", ALL_REDUCE,
                                          r, n + 3 * r))
        return tuple(rounds)
    if regime == "row-rs":
        if tracking:
            # AR front end (the tangent needs global A), sharded
            # rotation+Adam, then gather [G~^O; phi; partials] — G~ (the
            # new-basis projection) is already global via the rank-1
            # identity, so it is NOT re-gathered
            gathered = (r + 2) if recovery else r
            return (CollectiveRound("proj", ALL_REDUCE, r + 1, n),
                    CollectiveRound("gram_psum", ALL_REDUCE, r, n + 3 * r),
                    CollectiveRound("epilogue_gather", ALL_GATHER,
                                    gathered, n))
        # plain: scatter the projection so the Adam pass runs on the
        # (r, n/g) slice; the gather restores [G~; G~^O; phi; partials]
        gathered = (2 * r + 2) if recovery else r
        return (CollectiveRound("proj", REDUCE_SCATTER, r + 1, n),
                CollectiveRound("epilogue_gather", ALL_GATHER, gathered, n))
    raise ValueError(f"unknown regime {regime!r}")


@dataclass(frozen=True)
class StepProgram:
    """Declarative description of one bucket's optimizer step.

    Static and hashable (like ParamPlan); built at trace time, never
    enters the jitted graph.  ``axes`` empty means the plain (GSPMD /
    single-device) path: no shard_map, every round an identity.
    """

    regime: str                 # one of REGIMES
    axes: tuple                 # shard_map mesh axes; () = plain path
    shards: int                 # total group size over `axes`
    m: int
    n: int
    rank: int
    tracking: bool              # which step kind this program describes
    tracks: bool                # effective geometry: does the refresh
    #                             actually move the basis?  False for
    #                             plain steps AND for tracking steps of
    #                             frozen-subspace methods — such steps
    #                             declare (and the byte model charges)
    #                             the plain rounds
    recovery: bool
    rounds: tuple               # tuple[CollectiveRound, ...]
    grad_layout: str            # "replicated" | "column" | "row"
    state_layout: str           # M/V: "inherit" | "column" | "replicated"
    #                             | "slice" (n/g column slice per row shard)
    schedule: str               # tracking geometry: "tangent" | "gram"

    def round(self, name: str) -> Optional[CollectiveRound]:
        for rnd in self.rounds:
            if rnd.name == name:
                return rnd
        return None

    def collective_counts(self) -> dict[str, int]:
        """{HLO opcode: count} — what tests pin compiled programs
        against (see tests/test_mesh_fused.py / tests/test_program.py).
        Local rounds (kind ``grad-fused``) lower to no collective op, so
        they are excluded: a grad-fused program compiles to the same HLO
        collective counts as its untapped sibling."""
        counts: dict[str, int] = {}
        for rnd in self.rounds:
            if rnd.kind not in (ALL_REDUCE, REDUCE_SCATTER, ALL_GATHER):
                continue
            counts[rnd.kind] = counts.get(rnd.kind, 0) + 1
        return counts

    def collective_wire_bytes(self) -> int:
        """Per-device ring-model wire bytes of all rounds — the term the
        traffic byte model charges on top of local HBM bytes."""
        return sum(rnd.wire_bytes(self.shards) for rnd in self.rounds)

    def describe(self) -> str:
        """Human-readable program listing (tools/dump_program.py)."""
        lines = [f"StepProgram[{self.regime}] "
                 f"({'tracking' if self.tracking else 'plain'} step, "
                 f"m={self.m} n={self.n} r={self.rank} "
                 f"shards={self.shards} axes={self.axes or '-'})",
                 f"  grad layout : {self.grad_layout}",
                 f"  M/V layout  : {self.state_layout}",
                 f"  schedule    : {self.schedule}"]
        if not self.rounds:
            lines.append("  collectives : none")
        for rnd in self.rounds:
            lines.append(
                f"  collective  : {rnd.name:16s} {rnd.kind:15s} "
                f"payload ({rnd.rows}, {rnd.cols}) "
                f"= {rnd.payload_bytes} B, "
                f"wire {rnd.wire_bytes(self.shards)} B/device")
        return "\n".join(lines)


_GRAD_LAYOUT = {"replicated": "replicated", "column": "column",
                "row": "row", "row-rs": "row", "grass": "replicated"}
_STATE_LAYOUT = {"replicated": "inherit", "column": "column",
                 "row": "replicated", "row-rs": "slice", "grass": "inherit"}
_SCHEDULE = {"replicated": "tangent", "column": "tangent",
             "row": "gram", "row-rs": "gram", "grass": "tangent"}


def pick_row_flavor(m: int, n: int, r: int, group: int,
                    row_state: str = "auto") -> str:
    """THE row-family state-flavour policy: replicated M/V ("row") or
    the reduce-scatter slice layout ("row-rs").

    ``row_state`` forces a flavour ("replicated" / "reduce-scatter");
    "auto" compares the modeled per-device plain-step bytes (the
    k-1-of-k hot path) and takes the cheaper one.  row-rs additionally
    needs n divisible by the group (the scatter slices columns evenly) —
    a forced "reduce-scatter" degrades to "row" when it isn't.  Single
    implementation shared by :func:`build_program` and the layout
    builder (``distributed/sharding._row_bytes``), so the ranking and
    the executed flavour cannot drift.
    """
    if row_state == "replicated" or n % group != 0:
        return "row"
    if row_state == "reduce-scatter":
        return "row-rs"
    from repro.kernels import traffic  # lazy: traffic reads our rounds

    rs = traffic.sharded_row_rs_fused_step_bytes(m, n, r, group).total
    rep = traffic.sharded_row_fused_step_bytes(m, n, r, group).total
    return "row-rs" if rs < rep else "row"


def _row_flavor(cfg, m: int, n: int, r: int, group: int) -> str:
    return pick_row_flavor(m, n, r, group,
                           getattr(cfg, "row_state", "auto"))


def build_program(plan: plan_lib.ParamPlan, cfg, mesh, *,
                  tracking: bool, tapped: bool = False) -> StepProgram:
    """Classify one leaf (or bucket representative) into its StepProgram.

    This is the regime dispatch that used to live in
    ``subtrack.update.shard_info_for`` + ``plan.spec_regime``: a leaf
    enters a shard_map'd regime only when the optimizer was built with a
    mesh + specs, runs the fused kernels, and — on tracking steps — uses
    a shardable refresh method ("grassmann" / "none"; the SVD/random/Oja
    refreshes contract over all columns).  Row-family regimes route
    reorth-scrubbing tracking steps away (a QR of the row-sharded basis
    is not shard-local).  Everything else lowers to the replicated
    program: no shard_map, plain GSPMD propagation, zero declared
    rounds.

    ``tapped`` marks a plain step whose (r+1, n) [A; colnorms] panel
    arrives precomputed from the backward pass (the grad-fused path).
    Only the regimes whose projection the model-side tap can legally
    replace accept it — replicated, column (the tap is column-separable,
    see ``kernels.ops.grad_tap``) and grass; the row family contracts A
    over sharded rows the tap never sees, so ``tapped`` is ignored there
    and the caller falls back to the untapped program.
    """
    m, n, r = plan.m, plan.n, plan.rank
    method = getattr(cfg, "method", "grassmann")
    regime, axes = "replicated", ()
    if plan.mode == "lowrank" and method == "grass":
        # Grass never shard_maps: the top-r row selection contracts over
        # all columns (like the SVD refresh), so the leaf stays on plain
        # GSPMD propagation with the gather declared as a local round.
        regime = "grass"
    elif (mesh is not None and getattr(cfg, "use_kernels", False)
            and plan.mode == "lowrank"
            and not (tracking and cfg.method not in ("grassmann", "none"))):
        col = plan_lib.spec_column_axes(plan)
        row = plan_lib.spec_row_axes(plan)
        if col is not None:
            regime, axes = "column", col
        elif row is not None and not (tracking and cfg.method == "grassmann"
                                      and cfg.reorth_interval):
            regime, axes = "row", row
    shards = (int(np.prod([mesh.shape[a] for a in axes])) if axes else 1)
    if regime == "row":
        regime = _row_flavor(cfg, m, n, r, shards)
    recovery = bool(getattr(cfg, "recovery", True))
    tapped = tapped and not tracking and regime in ("replicated", "column",
                                                   "grass")
    # Rounds reflect the EFFECTIVE geometry: a tracking step whose
    # refresh method moves no basis (method="none" — the frozen-subspace
    # ablation) fires no geodesic collectives, so it declares (and the
    # byte model charges, and the HLO pins expect) the plain rounds.
    tracks = tracking and method in ("grassmann", "grass")
    return StepProgram(
        regime=regime, axes=tuple(axes), shards=shards, m=m, n=n, rank=r,
        tracking=tracking, tracks=tracks, recovery=recovery,
        rounds=regime_rounds(regime, m, n, r, shards, tracking=tracks,
                             recovery=recovery, tapped=tapped),
        grad_layout=_GRAD_LAYOUT[regime],
        state_layout=_STATE_LAYOUT[regime],
        schedule=_SCHEDULE[regime])


# ---------------------------------------------------------------------------
# Checkpoint-facing descriptors: the serializable face of a StepProgram
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StateDescriptor:
    """Serializable per-leaf record of HOW one optimizer-state leaf was
    (or will be) laid out: the StepProgram fields a checkpoint must carry
    so a restore under a *different* program can transpose the state
    (``repro.checkpoint.transpose``).

    ``kind`` is "lowrank" (a MatrixOptState leaf) or "dense" (plain Adam
    state).  For low-rank leaves, ``m, n, rank`` are the canonical
    (post-transpose) dims, ``method`` the refresh family ("grassmann"-like
    dense bases vs "grass" one-hot row selections — the two need a basis
    conversion, everything else is layout-only), and the layout fields
    mirror :class:`StepProgram`.  Not a pytree node: a descriptor is a
    LEAF of the descriptor pytree ``state_leaf_descriptors`` returns.
    """

    kind: str                     # "lowrank" | "dense"
    regime: str = "replicated"
    axes: tuple = ()
    shards: int = 1
    grad_layout: str = "replicated"
    state_layout: str = "inherit"
    schedule: str = "tangent"
    m: int = 0
    n: int = 0
    rank: int = 0
    batch_dims: int = 0
    method: str = "grassmann"

    def to_dict(self) -> dict:
        return {
            "kind": self.kind, "regime": self.regime,
            "axes": [str(a) for a in self.axes], "shards": int(self.shards),
            "grad_layout": self.grad_layout,
            "state_layout": self.state_layout, "schedule": self.schedule,
            "m": int(self.m), "n": int(self.n), "rank": int(self.rank),
            "batch_dims": int(self.batch_dims), "method": self.method,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "StateDescriptor":
        return cls(kind=d["kind"], regime=d.get("regime", "replicated"),
                   axes=tuple(d.get("axes", ())),
                   shards=int(d.get("shards", 1)),
                   grad_layout=d.get("grad_layout", "replicated"),
                   state_layout=d.get("state_layout", "inherit"),
                   schedule=d.get("schedule", "tangent"),
                   m=int(d.get("m", 0)), n=int(d.get("n", 0)),
                   rank=int(d.get("rank", 0)),
                   batch_dims=int(d.get("batch_dims", 0)),
                   method=d.get("method", "grassmann"))


def descriptor_for(plan: plan_lib.ParamPlan, cfg, mesh) -> StateDescriptor:
    """One leaf's StateDescriptor — built off the same ``build_program``
    classification the plain-step hot path runs under, so the recorded
    layout IS the executed one."""
    if plan.mode != "lowrank":
        return StateDescriptor(kind="dense")
    prog = build_program(plan, cfg, mesh, tracking=False)
    return StateDescriptor(
        kind="lowrank", regime=prog.regime, axes=prog.axes,
        shards=prog.shards, grad_layout=prog.grad_layout,
        state_layout=prog.state_layout, schedule=prog.schedule,
        m=prog.m, n=prog.n, rank=prog.rank, batch_dims=plan.batch_dims,
        method=getattr(cfg, "method", "grassmann"))


def state_leaf_descriptors(params, cfg, mesh=None, param_specs=None):
    """Pytree mirroring ``params`` of per-leaf :class:`StateDescriptor`.

    This is the accessor the checkpoint layer consumes: on save the
    descriptors are embedded in the manifest's ``extra_meta`` (source
    programs); on restore they are rebuilt for the *current* mesh/config
    and become the transpose targets.  ``cfg`` is any optimizer config —
    one without a ``rank`` (the dense baselines) yields all-dense
    descriptors, so every optimizer checkpoints through the same path.
    """
    import jax

    rank = getattr(cfg, "rank", 0)
    if not rank:
        return jax.tree.map(lambda _: StateDescriptor(kind="dense"), params)
    plans = plan_lib.make_plans(params, rank, specs=param_specs)
    return jax.tree.map(
        lambda plan: descriptor_for(plan, cfg, mesh), plans,
        is_leaf=lambda x: isinstance(x, plan_lib.ParamPlan))


# ---------------------------------------------------------------------------
# Runtime execution: named-round collectives inside the lowered step
# ---------------------------------------------------------------------------


class Exec:
    """Runtime face of a StepProgram inside the lowered per-matrix step.

    The math in ``subspace`` / ``lowrank_adam`` is written once against
    this interface: collectives are invoked by round name and are
    identities unless the program declares them, layout questions
    (``state_slice``, ``state_width``) answer from the program's
    declared layouts.  One instance is built per bucket per step kind
    (:func:`executor`); the replicated singleton :data:`NULL_EXEC` serves
    every exec-less caller (tests, benchmarks, the legacy jnp path).
    """

    def __init__(self, program: StepProgram):
        self.program = program
        axes = program.axes
        self.axis = None if not axes else (axes if len(axes) > 1
                                           else axes[0])

    # --- program data reads -------------------------------------------
    @property
    def schedule(self) -> str:
        return self.program.schedule

    @property
    def rows_sharded(self) -> bool:
        return self.program.grad_layout == "row"

    def has(self, name: str) -> bool:
        return self.program.round(name) is not None

    def state_width(self, n: int) -> int:
        """Columns of the Adam-state block this shard owns."""
        if self.program.state_layout == "slice":
            return n // self.program.shards
        return n

    # --- communication primitives -------------------------------------
    def _axis_index(self):
        import jax

        axes = self.program.axes
        idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        return idx

    def collective(self, name: str, x):
        """Execute round ``name`` on ``x`` — identity when the program
        does not declare it (or the program is unsharded)."""
        rnd = self.program.round(name)
        if rnd is None or rnd.kind == GRAD_FUSED or self.axis is None:
            return x
        import jax

        if rnd.kind == ALL_REDUCE:
            return jax.lax.psum(x, self.axis)
        if rnd.kind == REDUCE_SCATTER:
            return jax.lax.psum_scatter(x, self.axis,
                                        scatter_dimension=x.ndim - 1,
                                        tiled=True)
        if rnd.kind == ALL_GATHER:
            return jax.lax.all_gather(x, self.axis, axis=x.ndim - 1,
                                      tiled=True)
        raise ValueError(f"unknown collective kind {rnd.kind!r}")

    def psum(self, x):
        """Raw psum over the program axes (legacy unfused-path reductions
        that predate the fused rounds); identity when unsharded."""
        if self.axis is None:
            return x
        import jax

        return jax.lax.psum(x, self.axis)

    def state_slice(self, x):
        """This shard's Adam-state column block of a replicated-width
        array (identity unless the program's state layout is "slice")."""
        if self.program.state_layout != "slice" or self.axis is None:
            return x
        import jax

        n_loc = x.shape[-1] // self.program.shards
        return jax.lax.dynamic_slice_in_dim(
            x, self._axis_index() * n_loc, n_loc, axis=x.ndim - 1)


NULL_PROGRAM = StepProgram(
    regime="replicated", axes=(), shards=1, m=0, n=0, rank=0,
    tracking=False, tracks=False, recovery=True, rounds=(),
    grad_layout="replicated", state_layout="inherit", schedule="tangent")

NULL_EXEC = Exec(NULL_PROGRAM)


def executor(program: StepProgram) -> Exec:
    # Unsharded programs usually share the null executor, but a program
    # that declares rounds even at group 1 (grass gather, grad-fused
    # taps) needs its own Exec so ``has()`` answers from ITS rounds.
    if not program.axes and not program.rounds:
        return NULL_EXEC
    return Exec(program)


# ---------------------------------------------------------------------------
# Lowering: program -> (shard_map'd or plain) stacked runner
# ---------------------------------------------------------------------------


def lower(program: StepProgram, fn: Callable, *, mesh, batch_dims: int,
          with_param: bool, with_tap: bool = False,
          with_health: bool = False, kernels: bool = False) -> Callable:
    """Turn the per-bucket stacked step ``fn(g, st[, p][, tap]) ->
    (delta, st'[, diag])`` into the program's runner.

    Replicated programs return ``fn`` unchanged (plain jit path, GSPMD
    propagation) — unless ``kernels`` is set and ``mesh`` spans several
    devices: GSPMD cannot partition a compiled Pallas kernel, so the step
    then runs under a ``shard_map`` whose every spec is replicated (each
    device computes the whole leaf, as GSPMD replication would, and no
    collective is added).  Sharded programs wrap ``fn`` in ``shard_map`` with
    every in/out PartitionSpec derived from the program's declared
    layouts: the gradient/param/update panels follow ``grad_layout``, S
    shards with the gradient rows, M/V follow ``state_layout`` ("column"
    and "slice" both shard the global (r, n) state arrays along n —
    the slice layout simply pairs that with a row-sharded gradient),
    and ``lam_prev`` replicates.  ``with_tap`` appends the grad-fused
    (r+1, n) [A; colnorms] panel as the trailing argument; it shards
    along n with the gradient columns (the tap is column-separable), so
    inside the column regime each shard consumes exactly its slice.
    ``with_health`` appends a third output: the per-matrix
    (health.DIAG_SIZE,) diagnostic vector, replicated — sigma/theta and
    the guard flags derive from psum'd quantities, so every shard holds
    the same values under both tracking schedules.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if not program.axes:
        if kernels and mesh is not None and mesh.size > 1:
            return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                                 check_vma=False)
        return fn

    from repro.core.lowrank_adam import MatrixOptState

    ax = program.axes if len(program.axes) > 1 else program.axes[0]
    lead = (None,) * batch_dims
    if program.grad_layout == "column":
        gspec = P(*lead, None, ax)
        s_spec = P(*lead, None, None)
    else:                                        # row family
        gspec = P(*lead, ax, None)
        s_spec = P(*lead, ax, None)
    mv = {"column": P(*lead, None, ax),
          "replicated": P(*lead, None, None),
          "slice": P(*lead, None, ax)}[program.state_layout]
    stspec = MatrixOptState(S=s_spec, M=mv, V=mv, lam_prev=P(*lead))
    in_specs = (gspec, stspec) + ((gspec,) if with_param else ())
    if with_tap:
        in_specs = in_specs + (P(*lead, None, ax),)
    out_specs = (gspec, stspec)
    if with_health:
        out_specs = out_specs + (P(*lead, None),)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
