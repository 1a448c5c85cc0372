"""Record the scoped-trace fixture of ``test_chipbench_scopes.py`` on a TPU: a few
train steps of the tiny GQA cell of ``chipbench_tiny.py`` (the smoke
``qwen1.5-4b``) traced inside a window span, and the compiled step's op
map, kept to the instructions that the trace holds.

    python benchmarks/chip/tests/record_scoped_trace.py [--steps 2]

Writes ``data/scoped.xplane.pb.gz`` and ``data/scoped_ops.json`` beside
this file.  The trace's ``/host:metadata`` plane (the compiled modules'
protos, most of the file) is left out.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[2]
sys.path[:0] = [str(HERE), str(REPO), str(REPO / "src")]

import chipbench_tiny as tiny  # noqa: E402
from benchmarks.chip import bench, scopes  # noqa: E402
from benchmarks.chip import trace as trace_lib  # noqa: E402

CELL = "tiny-gqa.train"
FIXTURE = HERE / "data" / "scoped.xplane.pb.gz"


def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes):
    """(field number, encoded field) of a protobuf message's top level."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            i += n
        else:
            i += {1: 8, 5: 4}[wire]
        yield key >> 3, b[start:i]


def drop_plane(xspace: bytes, name: str) -> bytes:
    """An XSpace without its plane (field 1) named ``name`` (field 2)."""
    tag = b"\x12" + bytes([len(name)]) + name.encode()
    return b"".join(f for num, f in _fields(xspace)
                    if not (num == 1 and tag in f[:64]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("the fixture is a TPU trace: no TPU here", file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.build(Path(tmp) / "tree")
        cell = bench.find_cell(root, CELL, root / "benchmarks" / "chip")
        kind = bench.load_module(root / "benchmarks/chip/kinds/train.py")
        prog = kind.Program(cell.config, cell.workload)
        batch = prog.data(7)
        state, lr, _ = prog.start(7, batch)
        nxt = batch(4)
        step_ops = scopes.op_map(
            prog.step.lower(state, nxt, lr).compile().as_text())
        state, m = prog.step(state, nxt, lr)       # warm, outside the trace
        jax.block_until_ready(m)
        out = Path(tmp) / "trace"
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(out), profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
            for i in range(args.steps):
                state, m = prog.step(state, batch(5 + i), lr)
            jax.block_until_ready(m)
        jax.profiler.stop_trace()
        xplane = sorted(glob.glob(str(out / "plugins/profile/*/*.xplane.pb")))
        kept = drop_plane(Path(xplane[-1]).read_bytes(), "/host:metadata")
        FIXTURE.write_bytes(gzip.compress(kept, mtime=0))
        Path(xplane[-1]).write_bytes(kept)
        tr = trace_lib.load(xplane[-1])
    seen = {n.split(" = ", 1)[0].lstrip("%") for d in tr.ops for _, _, n in d}
    ops = {k: v for k, v in step_ops["ops"].items() if k in seen}
    (HERE / "data" / "scoped_ops.json").write_text(json.dumps(
        {"module": step_ops["module"], "steps": args.steps,
         "rank": cell.workload["optimizer"]["rank"], "ops": ops},
        sort_keys=True, separators=(",", ":")))
    print(f"recorded {args.steps} steps; {len(ops)} of "
          f"{len(step_ops['ops'])} instructions in the trace")
    return 0


if __name__ == "__main__":
    sys.exit(main())
