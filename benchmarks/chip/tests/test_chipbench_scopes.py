"""CPU tests of the per-layer reading through the program's named scopes
(``scopes.py``): the compiled text's op map, the attribution of a trace's
leaf ops, the readers on a trace recorded on a TPU v5e, and the step that
the reader compiles again being the step that ran."""

from __future__ import annotations

import base64
import gzip
import io
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[2]
sys.path.insert(0, str(REPO))

from benchmarks.chip import bench, scopes  # noqa: E402
from benchmarks.chip import trace as trace_lib  # noqa: E402

READERS = ("attention_ms.train", "mlp_ms.train", "embed_head_ms.train",
           "layer_scan_ms.train", "optimizer_ms.train", "recompute_ms.train")


def _reader(name):
    return bench.load_module(REPO / "benchmarks/chip/metrics" / f"{name}.py")


def _meta(op_name):
    return f', metadata={{op_name="{op_name}" source_file="x.py" ' \
           f'source_line=3 stack_frame_id=2}}'


FWD = "jit(train_step)/jvp(decoder)/while/body/closed_call"
BWD = "jit(train_step)/transpose(jvp(decoder))/while/body/closed_call"
HLO = f"""HloModule jit_train_step, is_scheduled=true, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

FileNames
1 "/src/x.py"

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}

%fused_computation (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %mul.0 = f32[8]{{0}} multiply(%param_0, %param_0){_meta(FWD + "/attention/mul")}
}}

ENTRY %main.9 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation{_meta(FWD + "/attention/mul")}
  %copy-start.12 = (f32[8]{{0}}, f32[8]{{0}}, u32[]) copy-start(%p)
  %copy-done.12 = f32[8]{{0}} copy-done(%copy-start.12)
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1, %copy-done.12), kind=kLoop, calls=%fused_computation{_meta(BWD + "/checkpoint/mlp/dot_general")}
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kLoop, calls=%fused_computation{_meta(BWD + "/checkpoint/rematted_computation/attention/dot_general")}
  %copy.4 = f32[8]{{0}} copy(%fusion.3){_meta(BWD[:-len("/closed_call")] + "/dynamic_update_slice")}
  %custom-call.5 = f32[8]{{0}} custom-call(%copy.4), custom_call_target="tpu_custom_call"{_meta("jit(train_step)/optimizer/jit(_where)/pjit(project)/pallas_call")}
  %add.6 = f32[8]{{0}} add(%custom-call.5, %p){_meta("jit(train_step)/jit(mlp)/add")}
  %gather.7 = f32[8]{{0}} gather(%add.6, %p){_meta("jit(train_step)/jvp(embed)/gather")}
  %mul.11 = f32[8]{{0}} multiply(%gather.7, %p){_meta("jit(train_step)/jvp()/mul")}
  %constant.13 = f32[] constant(0)
  ROOT %copy.8 = f32[8]{{0}} copy(%mul.11)
}}
"""


def test_op_map_of_a_compiled_text():
    m = scopes.op_map(HLO)
    assert m["module"] == "jit_train_step"
    assert m["ops"] == {
        "mul.0": ("attention", "forward"),
        "fusion.1": ("attention", "forward"),      # innermost of two
        "fusion.2": ("mlp", "backward"),
        "fusion.3": ("attention", "recompute"),
        "copy.4": ("decoder", "backward"),          # the scan's own
        "custom-call.5": ("optimizer", "forward"),
        "add.6": (None, "forward"),                 # a jit, not a scope
        "gather.7": ("embed", "forward"),           # under a transform
        "mul.11": (None, "forward"),                # outside every scope
        # no op_name: the first consumer's, else the first operand's
        "param_0": ("attention", "forward"), "p": ("attention", "forward"),
        "copy-start.12": ("mlp", "backward"),
        "copy-done.12": ("mlp", "backward"),
        "copy.8": (None, "forward"), "constant.13": (None, "forward")}


def test_stripped_text_ignores_names_and_metadata():
    renamed = HLO.replace("fusion.", "loop_multiply_fusion.").replace(
        "/attention/", "/").replace('"/src/x.py"', '"/elsewhere/x.py"')
    assert scopes.strip_metadata(renamed) == scopes.strip_metadata(HLO)
    other = HLO.replace("multiply(%param_0", "add(%param_0")
    assert scopes.strip_metadata(other) != scopes.strip_metadata(HLO)
    assert "metadata" not in scopes.strip_metadata(HLO)


def _kernel(path, ops=1):
    """A serialized module standing for a Pallas kernel's body: ``ops``
    operations located in the file ``path``."""
    from jax._src.lib.mlir import ir

    with ir.Context() as ctx, ir.Location.file(path, 3, 1):
        ctx.allow_unregistered_dialects = True
        module = ir.Module.create()
        with ir.InsertionPoint(module.body):
            for _ in range(ops):
                ir.Operation.create("kernel.op")
        out = io.BytesIO()
        module.operation.write_bytecode(out)
    return base64.b64encode(out.getvalue()).decode()


def test_kernel_bodies_are_compared_without_locations():
    call = ('  %k.1 = f32[8]{0} custom-call(%p), custom_call_target='
            '"tpu_custom_call", backend_config={"custom_call_config":'
            '{"body":"BODY"}}')
    here, there, more = (scopes.strip_metadata(
        call.replace("BODY", _kernel(path, ops)))
                         for path, ops in (("/a/k.py", 1), ("/b/k.py", 1),
                                           ("/a/k.py", 2)))
    assert here == there != more


def _hand_trace():
    op = lambda name: f"%{name} = f32[8]{{0}} op()"  # noqa: E731
    kernel = ('%custom-call.5 = f32[8]{0} custom-call(f32[8]{0} %copy.4), '
              'custom_call_target="tpu_custom_call"')
    return trace_lib.Trace(
        window=(0.0, 10.0),
        ops=[[(1.0, 1.5, op("fusion.1")),
              (2.0, 4.0, op("while.9")),          # a loop: holds two ops
              (2.1, 2.9, op("fusion.2")), (3.0, 3.5, op("fusion.3")),
              (4.2, 4.4, kernel),
              (4.5, 4.6, op("gone.10")),           # not in the map
              (4.7, 4.8, op("mul.11")),            # in no scope
              (4.85, 4.95, op("copy-done.12")),    # the mlp's copy
              (6.0, 6.5, op("fusion.1"))]],        # in another module
        modules=[[(0.9, 5.0, "jit_train_step(123)"),
                  (5.5, 7.0, "jit__lambda(9)")]],
        spans=[])


def test_split_of_a_hand_made_trace():
    sp = scopes.split(_hand_trace(), scopes.op_map(HLO))
    assert sp["runs"] == 1
    assert sp["by"] == pytest.approx({
        ("attention", "forward"): 0.5, ("mlp", "backward"): 0.9,
        ("attention", "recompute"): 0.5, ("optimizer", "forward"): 0.2,
        (None, "forward"): 0.1})
    assert sp["unmatched"] == pytest.approx(0.1)
    assert sp["kernels"] == pytest.approx(0.2)
    assert sp["total"] == pytest.approx(2.3)
    assert sp["unscoped"] == pytest.approx({"%mul.11": 0.1})


def _rec(tr, step_ops, steps, rank=512):
    return {"trace": tr, "host": {"kind": "train", "steps": steps,
                                  "step_ops": step_ops},
            "peaks": None, "config": None,
            "workload": {"optimizer": {"rank": rank}}}


def test_readers_on_a_hand_made_trace(capsys):
    rec = _rec(_hand_trace(), scopes.op_map(HLO), steps=2)
    got = {n: _reader(n).read(rec) for n in READERS}
    assert got == pytest.approx({
        "attention_ms.train": 500.0, "mlp_ms.train": 450.0,
        "embed_head_ms.train": 0.0, "layer_scan_ms.train": 0.0,
        "optimizer_ms.train": 100.0, "recompute_ms.train": 250.0})
    err = capsys.readouterr().err
    assert "not in the op map 4.348%" in err, err
    assert err.count("[scopes] 1 executions") == 1      # printed once


def test_a_program_without_scopes_reads_nothing(capsys):
    plain = scopes.op_map(HLO.replace("attention/", "").replace(
        "mlp/", "").replace("optimizer/", "").replace("(decoder)", "(f)")
        .replace("(embed)", "(g)"))
    rec = _rec(_hand_trace(), plain, steps=2)
    assert all(_reader(n).read(rec) is None for n in READERS)
    assert "names none of" in capsys.readouterr().err


# --- a scoped train step traced on a TPU v5e ---------------------------------


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two steps of the tiny GQA cell's train step traced on a TPU v5e,
    and the step's op map (``record_scoped_trace.py``)."""
    step_ops = json.loads((HERE / "data" / "scoped_ops.json").read_text())
    xplane = tmp_path_factory.mktemp("scoped") / "scoped.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (HERE / "data" / "scoped.xplane.pb.gz").read_bytes()))
    return trace_lib.load(str(xplane)), step_ops


def test_readers_on_a_recorded_tpu_trace(recorded):
    tr, step_ops = recorded
    rec = _rec(tr, step_ops, step_ops["steps"], step_ops["rank"])
    got = {n: _reader(n).read(rec) for n in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["attention_ms.train"] > 0 and got["mlp_ms.train"] > 0
    assert got["optimizer_ms.train"] > 0 and got["recompute_ms.train"] > 0


def test_recorded_readings_are_pinned(recorded):
    """The readers on the recorded trace read what they read before a
    family could add scope names."""
    tr, step_ops = recorded
    rec = _rec(tr, step_ops, step_ops["steps"], step_ops["rank"])
    assert {n: _reader(n).read(rec) for n in READERS} == {
        "attention_ms.train": 0.02328699999999337,
        "mlp_ms.train": 0.00525800000000104,
        "embed_head_ms.train": 0.006856000000002999,
        "layer_scan_ms.train": 0.003746499999970898,
        "optimizer_ms.train": 0.03224499999998978,
        "recompute_ms.train": 0.00679500000001082}


def test_recorded_split_adds_up_to_the_step_module(recorded):
    tr, step_ops = recorded
    sp = scopes.split(tr, step_ops)
    assert sp["runs"] == step_ops["steps"]
    assert sp["total"] == pytest.approx(
        sum(sp["by"].values()) + sp["unmatched"])
    # the step module's leaf-op time, counted without the op map
    runs = [(s, e) for s, e, n in tr.modules[0]
            if n.startswith(step_ops["module"] + "(")]
    inside = sum(e - s for s, e, _ in trace_lib.leaf_ops(tr.ops[0])
                 if any(a <= 0.5 * (s + e) <= b for a, b in runs))
    assert sp["total"] == pytest.approx(inside)
    assert sp["unmatched"] <= 0.01 * sp["total"]


# --- the step the reader compiles is the step that ran ------------------------


def test_compiled_again_is_the_step_that_ran(tmp_path, monkeypatch):
    import chipbench_tiny as tiny

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    root = tiny.build(tmp_path / "tree")
    cell = bench.find_cell(root, "tiny-mla.train", root / "benchmarks/chip")
    kind = bench.load_module(root / "benchmarks/chip/kinds/train.py")
    prog = kind.Program(cell.config, cell.workload)
    batch = prog.data(11)
    state, lr, _ = prog.start(11, batch)
    ran = prog.step.lower(state, batch(4), lr).compile().as_text()
    again = scopes.compiled_step(cell.config, cell.workload,
                                 root / "benchmarks/chip")
    assert scopes.op_map(again) == scopes.op_map(ran)
    assert scopes.strip_metadata(again) == scopes.strip_metadata(ran)
