"""Print the lowered StepProgram for a config / mesh / leaf shape — the
debugging story for hot-path regime selection.

For a given parameter-leaf shape, rank, PartitionSpec and mesh, this
prints what the optimizer will actually lower per step kind: the chosen
regime, the gradient/state layouts, the tracking schedule, every
collective round (name, kind, payload shape, per-device ring wire
bytes), and the modeled per-device HBM+wire bytes of the fused step vs
the paper-literal schedule distributed the same way.

Examples::

    PYTHONPATH=src python tools/dump_program.py \
        --shape 2048 4097 --rank 64 --spec model,None --mesh model=16,data=2

    PYTHONPATH=src python tools/dump_program.py \
        --shape 1024 2560 --rank 128 --spec x,None --mesh x=8 \
        --row-state replicated

    # why does this leaf NOT shard?  (indivisible n, tiny mesh, ...)
    PYTHONPATH=src python tools/dump_program.py \
        --shape 512 384 --rank 128 --spec None,x --mesh x=8

No devices are needed: programs are static data (AbstractMesh).
"""

from __future__ import annotations

import argparse

from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core import plan as plan_lib
from repro.core import program as program_lib
from repro.core.subtrack import LowRankConfig
from repro.kernels import traffic


def parse_mesh(text: str) -> AbstractMesh:
    """"model=16,data=2" -> AbstractMesh((16, 2), ("model", "data"))."""
    pairs = []
    for part in text.split(","):
        name, _, size = part.partition("=")
        if not size:
            raise SystemExit(f"bad mesh entry {part!r}; want name=size")
        pairs.append((name.strip(), int(size)))
    return AbstractMesh(tuple(size for _, size in pairs),
                        tuple(name for name, _ in pairs))


def parse_spec(text: str | None, ndim: int) -> P:
    """"model,None" / "None,x" / "x" -> PartitionSpec (None-padded)."""
    if text is None:
        return None
    entries = []
    for part in text.split(","):
        part = part.strip()
        entries.append(None if part in ("None", "none", "-", "") else part)
    entries += [None] * (ndim - len(entries))
    return P(*entries)


def modeled_bytes(prog: program_lib.StepProgram, *,
                  grad_bytes: int, param_bytes: int,
                  recovery: bool = True) -> list[str]:
    """Fused vs paper-literal per-device byte lines for the program.

    Keyed on the program's EFFECTIVE geometry (``prog.tracks``), not the
    step kind: a tracking step whose refresh moves no basis (method
    "none") declares — and must be modeled as — the plain schedule, so
    the bytes printed here always match the rounds printed above them.
    A program carrying the ``grad_tap`` round is modeled tap-fed
    (repro.kernels.traffic.gradfused_step_bytes — no projection pass)."""
    kw = dict(grad_bytes=grad_bytes, param_bytes=param_bytes)
    m, n, r = prog.m, prog.n, prog.rank
    tracks = prog.tracks
    if prog.regime in ("replicated", "grass"):
        if not tracks and prog.round("grad_tap") is not None:
            gf = traffic.gradfused_step_bytes(m, n, r, recovery=recovery,
                                              **kw)
            unf = traffic.unfused_step_bytes(m, n, r, **kw)
            fus = traffic.fused_step_bytes(m, n, r, **kw)
            return [f"  modeled local bytes : grad-fused {gf.total:,} vs "
                    f"paper-literal {unf.total:,} "
                    f"(ratio {gf.total / unf.total:.3f}; fused-without-tap "
                    f"would be {fus.total / unf.total:.3f} — the tap "
                    "replaces the projection pass)"]
        fus = (traffic.tracking_fused_step_bytes(m, n, r, **kw) if tracks
               else traffic.fused_step_bytes(m, n, r, **kw))
        unf = (traffic.tracking_unfused_step_bytes(m, n, r, **kw)
               if tracks else traffic.unfused_step_bytes(m, n, r, **kw))
        note = ("grass — selection gather, no wire term"
                if prog.regime == "grass" else "replicated — no wire term")
        return [f"  modeled local bytes : fused {fus.total:,} vs "
                f"paper-literal {unf.total:,} "
                f"(ratio {fus.total / unf.total:.3f}; {note})"]
    fus_fn, unf_fn = traffic._REGIME_MODEL_FNS[(prog.regime, tracks)]
    fus = fus_fn(m, n, r, prog.shards, **kw)
    unf = unf_fn(m, n, r, prog.shards, **kw)
    return [
        f"  modeled bytes/device: fused {fus.total:,} "
        f"(local {fus.local.total:,} + wire {fus.collective_bytes:,}) vs "
        f"paper-literal {unf.total:,}",
        f"  fused/literal ratio : {fus.total / unf.total:.3f}",
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--shape", type=int, nargs="+", required=True,
                    help="parameter leaf shape, e.g. --shape 2048 4097 "
                         "or --shape 3 1024 2560 (leading stack dims ok)")
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--spec", default=None,
                    help="comma-separated PartitionSpec entries in the "
                         "LEAF's layout, e.g. 'model,None' or 'None,x' "
                         "(default: unsharded)")
    ap.add_argument("--mesh", default=None,
                    help="mesh axes as name=size pairs, e.g. "
                         "'model=16,data=2' (default: no mesh — the "
                         "replicated program)")
    ap.add_argument("--method", default="grassmann")
    ap.add_argument("--row-state", default="auto",
                    choices=["auto", "replicated", "reduce-scatter"])
    ap.add_argument("--reorth-interval", type=int, default=0)
    ap.add_argument("--no-recovery", action="store_true")
    ap.add_argument("--grad-fused", action="store_true",
                    help="build the tapped program: plain steps carry the "
                         "grad_tap round (backward-pass [A; colnorms] "
                         "panel) where the regime admits it")
    ap.add_argument("--grad-bytes", type=int, default=4,
                    help="gradient dtype width (2 for bf16)")
    ap.add_argument("--param-bytes", type=int, default=4)
    args = ap.parse_args(argv)

    shape = tuple(args.shape)
    mesh = parse_mesh(args.mesh) if args.mesh else None
    spec = parse_spec(args.spec, len(shape))
    plan = plan_lib.plan_for_shape(shape, args.rank, spec=spec)
    cfg = LowRankConfig(rank=args.rank, method=args.method,
                        use_kernels=True, row_state=args.row_state,
                        reorth_interval=args.reorth_interval,
                        recovery=not args.no_recovery)

    print(f"leaf shape {shape}  spec {spec}  rank {args.rank}  "
          f"mesh {args.mesh or '-'}")
    if plan.mode != "lowrank":
        print("plan: DENSE (min trailing dim <= rank) — plain Adam, "
              "no program")
        return 0
    print(f"canonical (m, n) = ({plan.m}, {plan.n})"
          + ("  [transposed]" if plan.transpose else "")
          + (f"  stack dims = {plan.batch_dims}" if plan.batch_dims
             else ""))
    for tracking, title in ((False, "plain step (k-1 of k)"),
                            (True, "tracking step (1 of k)")):
        prog = program_lib.build_program(plan, cfg, mesh,
                                         tracking=tracking,
                                         tapped=args.grad_fused)
        print(f"\n== {title} ==")
        print(prog.describe())
        for line in modeled_bytes(prog, grad_bytes=args.grad_bytes,
                                  param_bytes=args.param_bytes,
                                  recovery=not args.no_recovery):
            print(line)
        if prog.regime == "replicated" and mesh is not None:
            print("  (replicated: leaf/config not admissible for any "
                  "shard_map regime — check spec orientation, the "
                  "n/g >= 2r / m/g >= 2r gates, lead-dim sharding, or a "
                  "non-shardable refresh method on tracking steps)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
