"""Device time of the train step's layers, read through the program's own
named scopes.

The program names its layers with ``jax.named_scope`` (``SCOPES``).  A
scope reaches the compiled step only as each HLO instruction's ``op_name``
metadata, such as ``jit(train_step)/transpose(jvp(decoder))/while/body/
closed_call/checkpoint/rematted_computation/attention/dot_general``; the
trace names its op events by instruction (``%fusion.287 = ...``) and
carries no metadata.  So the compiled step's text is parsed into a map
{instruction: (scope, pass)}, and each leaf op of the trace that ran inside
an execution of the step's module is attributed through it.  The scope is
the innermost of ``SCOPES``, and of the configuration's family's
``EXTRA_SCOPES`` (``refs/<family>.py``), on the path (a transform may wrap
a component: ``jvp(decoder)``); the pass is ``recompute`` under
``rematted_computation``, ``backward`` under a ``transpose(...)``
transform, ``forward`` otherwise.
A fusion carries its root's ``op_name``, so a fusion across a boundary
counts once, on its root's side.

The map comes from compiling the step again after the window, from the
cell's configuration and workload alone (the persistent compilation cache
makes that a load); ``host["step_ops"]``, where a caller has put a map
there, is used instead.
"""

from __future__ import annotations

import base64
import hashlib
import re
import sys
import time
from pathlib import Path

from benchmarks.chip import bench
from benchmarks.chip import model as model_lib
from benchmarks.chip import trace as trace_lib

SCOPES = ("embed", "decoder", "attention", "mlp", "head_loss", "clip",
          "optimizer", "apply", "warm_start")
PASSES = ("forward", "backward", "recompute")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_WRAP = re.compile(r"^(\w+)\((.*)\)$")
# the stack-frame index that heads a compiled module's text
_FRAME_SECTIONS = frozenset({"FileNames", "FunctionNames", "FileLocations",
                             "StackFrames"})


def scope_of(op_name: str, extra=()) -> tuple[str | None, str]:
    """``op_name`` -> (innermost scope of ``SCOPES`` or of the family's
    ``extra`` names, or None; pass)."""
    scope, backward, recompute = None, False, False
    for comp in op_name.split("/"):
        backward |= comp.startswith("transpose(")
        recompute |= comp == "rematted_computation"
        m = _WRAP.match(comp)
        while m and m.group(1) not in ("jit", "pjit"):
            comp = m.group(2)
            m = _WRAP.match(comp)
        if comp in SCOPES or comp in extra:
            scope = comp
    return scope, ("recompute" if recompute else
                   "backward" if backward else "forward")


def op_map(hlo_text: str, extra=()) -> dict:
    """A compiled module's text -> {"module": name, "ops": {instruction:
    (scope, pass)}}.  An instruction that the compiler added without an
    ``op_name`` (a layout copy, an asynchronous copy's start and done)
    works for its consumer: it takes the scope and pass of its first
    consumer that has them, else of its first operand, else none."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    ops, operands, users = {}, {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, op_name = m.group(1), _OP_NAME.search(line)
        ops[name] = scope_of(op_name.group(1), extra) if op_name else None
        operands[name] = [r for r in _REF.findall(line[m.end():])
                          if r != name]
        for r in operands[name]:
            users.setdefault(r, []).append(name)
    todo = [n for n, v in ops.items() if v is None]
    for near in (users, operands):          # consumers first, to the end
        while todo:
            left = []
            for n in todo:
                ops[n] = next((ops[x] for x in near.get(n, [])
                               if ops.get(x) is not None), None)
                if ops[n] is None:
                    left.append(n)
            if len(left) == len(todo):
                break
            todo = left
    return {"module": module,
            "ops": {n: v or (None, "forward") for n, v in ops.items()}}


def _kernel_without_locations(body: str) -> str:
    """A Pallas kernel's serialized Mosaic module (base64 bytecode) ->
    the sha256 of its text without locations, which name the source's
    files and the op names.  A body that does not parse is kept."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir, passmanager

    try:
        with ir.Context() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(body))
            passmanager.PassManager.parse(
                "builtin.module(strip-debuginfo)").run(module.operation)
            return hashlib.sha256(str(module).encode()).hexdigest()
    except Exception as e:       # a fingerprint, never a failure
        print(f"[scopes] a kernel body kept as it is: {e!r:.200}",
              file=sys.stderr)
        return body


def strip_metadata(hlo_text: str) -> str:
    """A compiled module's text without its metadata: no ``metadata={...}``
    attribute, no stack-frame index, every ``%name`` renamed by order of
    first appearance (instruction names are drawn from the op names), and
    each Pallas kernel's body without locations.  Two compiles of one
    program read the same here, from any checkout."""
    names: dict[str, str] = {}
    out = []
    for line in hlo_text.splitlines():
        if line in _FRAME_SECTIONS or re.match(r"\d+ ", line):
            continue
        line = re.sub(r",? metadata=\{[^}]*\}", "", line)
        line = _KERNEL_BODY.sub(lambda m: '"body":"%s"' % (
            _kernel_without_locations(m.group(1))), line)
        out.append(re.sub(r"%[\w.\-]+", lambda m: names.setdefault(
            m.group(0), f"%i{len(names)}"), line))
    return "\n".join(out)


def compiled_step(conf: dict, wl: dict,
                  bench_dir: Path = bench.BENCH_DIR) -> str:
    """The optimized HLO text of the train step a training cell runs, built
    by the cell's own kind (``kinds/<kind>.py``) and compiled from shapes
    alone: the state as a step leaves it (replicated over the program's
    one-device mesh), the batch and the learning rate as the window feeds
    them (placed on no device in particular)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.context import get_mesh_context
    from repro.launch.steps import TrainState

    kind = bench.load_module(bench_dir / "kinds" / f"{wl['kind']}.py")
    prog = kind.Program(conf, wl, bench_dir)
    params = jax.eval_shape(lambda: model_lib.make_params(conf, 0,
                                                          bench_dir))
    state = TrainState(params=params,
                       opt=jax.eval_shape(prog.init_opt, params))
    free = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       weak_type=x.weak_type), t)
    batch = free(jax.eval_shape(prog.data(0), 0))
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    state = jax.eval_shape(prog.warm, free(state), batch)[0]
    placed = NamedSharding(get_mesh_context().mesh, PartitionSpec())
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=x.weak_type, sharding=placed),
        jax.eval_shape(prog.step, state, batch, lr)[0])
    return prog.step.lower(state, batch, lr).compile().as_text()


def step_op_map(rec) -> dict:
    """The op map of the cell's step, compiled once per run.  Prints the
    fingerprint of the step's text without metadata, by which two
    revisions that differ in op names alone read as one program."""
    host = rec["host"]
    if "step_ops" not in host:
        t0 = time.perf_counter()
        conf, bench_dir = rec["config"], rec["bench_dir"]
        extra = getattr(model_lib.family(conf, bench_dir), "EXTRA_SCOPES", ())
        text = compiled_step(conf, rec["workload"], bench_dir)
        host["step_ops"] = op_map(text, tuple(extra))
        print(f"[scopes] {host['step_ops']['module']} compiled again in "
              f"{time.perf_counter() - t0:.1f}s; without metadata sha256 "
              f"{hashlib.sha256(strip_metadata(text).encode()).hexdigest()}",
              file=sys.stderr, flush=True)
    return host["step_ops"]


def _instruction(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def step_leaf_ops(tr, module: str):
    """Per chip: the leaf ops (a loop's event spans its body's) that ran
    inside an execution of ``module``."""
    out = []
    for chip, ops in enumerate(tr.ops):
        runs = [(s, e) for s, e, n in tr.modules[chip]
                if n.split("(", 1)[0] == module]
        keep, i = [], 0
        for s, e, n in trace_lib.leaf_ops(ops):
            mid = 0.5 * (s + e)
            while i < len(runs) and runs[i][1] < mid:
                i += 1
            if i < len(runs) and runs[i][0] <= mid:
                keep.append((s, e, n))
        out.append(keep)
    return out


def split(tr, step_ops: dict) -> dict:
    """Seconds of the step module's leaf ops, averaged over chips: by
    (scope, pass), the part whose instruction is not in the map, the
    Pallas kernels inside ``optimizer``, and the total; and the module's
    executions in the window."""
    ops = step_ops["ops"]
    chips = len(tr.ops) or 1
    by: dict[tuple, float] = {}
    unscoped: dict[str, float] = {}
    unmatched = kernels = total = 0.0
    for leaf in step_leaf_ops(tr, step_ops["module"]):
        for s, e, n in leaf:
            dt = (e - s) / chips
            total += dt
            key = ops.get(_instruction(n))
            if key is None:
                unmatched += dt
                continue
            key = tuple(key)
            by[key] = by.get(key, 0.0) + dt
            if key[0] is None:
                short = trace_lib.short_name(n)
                unscoped[short] = unscoped.get(short, 0.0) + dt
            if key[0] == "optimizer" and KERNEL_TARGET in n:
                kernels += dt
    runs = sum(1 for s, e, n in (tr.modules[0] if tr.modules else [])
               if n.split("(", 1)[0] == step_ops["module"])
    return {"by": by, "unmatched": unmatched, "kernels": kernels,
            "total": total, "runs": runs, "unscoped": unscoped}


def _report(sp: dict, steps: int, rec) -> None:
    ms = lambda s: 1e3 * s / steps  # noqa: E731
    err = sys.stderr
    total = sp["total"] or 1.0
    scoped = sum(v for (s, _), v in sp["by"].items() if s is not None)
    print(f"[scopes] {sp['runs']} executions of the step module, {steps} "
          f"steps; {ms(sp['total']):.3f} ms of leaf ops a step; not in the "
          f"op map {100 * sp['unmatched'] / total:.3f}%, in no scope "
          f"{100 * (sp['total'] - sp['unmatched'] - scoped) / total:.3f}%",
          file=err)
    print("[scopes] ms per step  " + "".join(f"{p:>11}" for p in PASSES),
          file=err)
    extra = sorted({s for s, _ in sp["by"] if s is not None} - set(SCOPES))
    for scope in SCOPES + tuple(extra) + (None,):
        row = [sp["by"].get((scope, p), 0.0) for p in PASSES]
        if any(row):
            print(f"[scopes] {str(scope):12s}" + "".join(
                f"{ms(x):11.3f}" for x in row), file=err)
    top = sorted(sp["unscoped"].items(), key=lambda x: -x[1])[:5]
    print("[scopes] most time in no scope: " + ", ".join(
        f"{n} {ms(t):.3f} ms" for n, t in top), file=err)
    kernel = bench.load_module(bench.BENCH_DIR / "metrics" /
                               "opt_kernel_ms.train.py")
    listed = kernel.kernel_seconds(rec["trace"],
                                   rec["workload"]["optimizer"]["rank"])
    print(f"[scopes] Pallas kernels in optimizer {ms(sp['kernels']):.3f} "
          f"ms a step; opt_kernel_ms.train's list {ms(listed):.3f}",
          file=err, flush=True)


def reading(rec) -> dict | None:
    """The split of a traced training window, or None where there is no
    trace or the step names none of ``SCOPES`` (a program without them)."""
    tr, host = rec["trace"], rec["host"]
    if tr is None or host.get("kind") != "train" or not host["steps"] \
            or not tr.ops:
        return None
    if "step_split" not in host:
        step_ops = step_op_map(rec)
        named = any(s is not None for s, _ in step_ops["ops"].values())
        host["step_split"] = split(tr, step_ops) if named else None
        if named:
            _report(host["step_split"], host["steps"], rec)
        else:
            print(f"[scopes] {step_ops['module']} names none of {SCOPES}",
                  file=sys.stderr)
    return host["step_split"]


def layer_ms(rec, names=None, passes=PASSES) -> float | None:
    """Device milliseconds per step of the window in the scopes ``names``
    (None in it names the ops in no scope; ``names`` None counts every op
    of the map) and ``passes``."""
    sp = reading(rec)
    if sp is None:
        return None
    seconds = sum(v for (s, p), v in sp["by"].items()
                  if (names is None or s in names) and p in passes)
    return 1e3 * seconds / rec["host"]["steps"]
