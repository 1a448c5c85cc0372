"""The model family a configuration names (``"reference"``) is all that
the harness knows of a model's shape: its readings are pinned to those
recorded before the family interface existed, a family is added by files
alone, an absent one is refused by name, and the generic files name no
family's parameters."""

from __future__ import annotations

import hashlib
import json
import re
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
HARNESS = HERE.parent
REPO = HERE.parents[2]
sys.path.insert(0, str(REPO))

from benchmarks.chip import bench, flops, scopes  # noqa: E402
from benchmarks.chip import model as model_lib  # noqa: E402
from benchmarks.chip.kinds import train as train_kind  # noqa: E402
from benchmarks.chip.refs import subtrack  # noqa: E402

# Readings of the harness recorded before the family interface existed:
# the two cells' layouts, FLOPs and low-rank slices, and the plain
# reference on the tiny cells of ``chipbench_tiny.py`` (batches drawn by
# numpy from the seed).
PINNED = json.loads((HERE / "data" / "pinned_readings.json").read_text())
GENERIC = ("kinds/train.py", "model.py", "flops.py", "refs/subtrack.py")


def _conf(name):
    return json.loads((HARNESS / "configs" / f"{name}.json").read_text())


# --- the cells' sizes, pinned ------------------------------------------------


@pytest.mark.parametrize("name", sorted(PINNED["configs"]))
@pytest.mark.parametrize("what", ["param_shapes", "flops", "lowrank"])
def test_cell_sizes_are_pinned(name, what):
    conf, pin = _conf(name), PINNED["configs"][name]
    if what == "param_shapes":
        got = {".".join(p): list(s) for p, s in model_lib.leaves(
            model_lib.param_shapes(conf))}
        assert got == pin["param_shapes"]
    elif what == "flops":
        assert flops.train_flops_per_token(conf, 2048) == pin[
            "train_flops_per_token_2048"]
    else:
        got = sorted(list(k) + [v] for k, v in Counter(
            train_kind.lowrank_shapes(conf, 512)).items())
        assert got == pin["lowrank_shapes_512"]


# --- the plain reference on the tiny cells, pinned bit for bit ---------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    import chipbench_tiny as tiny

    return tiny.build(tmp_path_factory.mktemp("family") / "tree")


@pytest.mark.parametrize("case", sorted(PINNED["reference"]))
def test_reference_readings_are_pinned(tree, case):
    import jax.numpy as jnp

    pin = PINNED["reference"][case]
    bench_dir = tree / "benchmarks" / "chip"
    wl = json.loads((bench_dir / "workloads" / f"{pin['cell']}.json")
                    .read_text())
    conf = json.loads((bench_dir / "configs" / f"{wl['config']}.json")
                      .read_text())
    rng = np.random.default_rng(pin["seed"])
    tokens = [rng.integers(0, conf["vocab_size"], (wl["batch"],
                                                   wl["seq_len"]),
                           dtype=np.int32) for _ in range(4)]
    S = wl["seq_len"]
    rows = np.arange(S) < S // 2 if pin["half_batch"] else None
    fam = model_lib.family(conf, bench_dir)
    got = subtrack.train(fam.Model(conf, pin["fmt"]), fam.stack_axes,
                         model_lib.make_params(conf, pin["seed"], bench_dir),
                         [jnp.asarray(t) for t in tokens],
                         dict(wl["optimizer"]), rows=rows)
    got["dense"] = sorted(got["dense"])
    want = pin["readings"]
    assert got == want


# --- slices by every stack axis ----------------------------------------------


class _Bank:
    """A toy family's model: a bank of E (m, n) matrices and a bias,
    loss = mean((tanh(x W_e) + b)^2) in float32; the bank's leaf has one
    stack axis.
    ``per_expert`` splits the bank into one piece per matrix."""

    def __init__(self, x, per_expert):
        import jax

        self.x, self.per_expert = x, per_expert
        self.loss = jax.jit(jax.value_and_grad(self._loss))

    @staticmethod
    def _loss(w, x):
        import jax.numpy as jnp

        h = jnp.tanh(jnp.einsum("bm,emn->ebn", x, w["bank"])) + w["bias"]
        return jnp.mean(h * h)

    def split(self, params):
        if not self.per_expert:
            return {((), ()): dict(params)}
        pieces = {((), ()): {"bias": params["bias"]}}
        for e in range(params["bank"].shape[0]):
            pieces[((), (e,))] = {"bank": params["bank"][e]}
        return pieces

    def grads(self, pieces, tokens, rows=None):
        import jax
        import jax.numpy as jnp

        bank = [v["bank"] for k, v in sorted(pieces.items()) if k[1]]
        w = {"bias": pieces[((), ())]["bias"],
             "bank": jnp.stack(bank) if bank else pieces[((), ())]["bank"]}
        loss, g = self.loss(jax.tree.map(lambda a: a.astype(jnp.float32),
                                         w), self.x + tokens)
        if self.per_expert:
            yield ((), ()), {"bias": g["bias"]}
            for e in range(g["bank"].shape[0]):
                yield ((), (e,)), {"bank": g["bank"][e]}
        else:
            yield ((), ()), g
        yield None, float(loss)


def test_a_stacked_leaf_steps_slice_by_slice():
    """One piece holding a bank of three matrices reads as three pieces of
    one matrix each: the same slices, names, low-rank rule and numbers."""
    import jax
    import jax.numpy as jnp

    E, m, n, rank = 3, 24, 40, 8
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"bank": (jax.random.normal(k[0], (E, m, n)) / 5).astype(
        jnp.bfloat16), "bias": jnp.zeros((n,), jnp.bfloat16)}
    x = jax.random.normal(k[1], (4, m))
    batches = [0.1 * i * jnp.ones((4, m)) for i in range(4)]
    opt = dict(rank=rank, beta1=0.9, beta2=0.999, eps=1e-8, scale=0.25,
               zeta=1.01, lr=1e-3, clip_norm=1e9,
               init={"method": "randomized", "seed": 0, "oversample": 4,
                     "power_iters": 1})
    axes = lambda path: 1 if path == ("bank",) else 0  # noqa: E731
    whole, split = (subtrack.train(_Bank(x, per), axes, dict(params),
                                   batches, opt) for per in (False, True))
    assert sorted(whole["first_grad"]) == ["bank.0", "bank.1", "bank.2",
                                           "bias"]
    assert whole["dense"] == split["dense"] == {"bias"}
    for key in ("losses", "first_grad", "first_raw", "change"):
        assert whole[key] == split[key], key
    assert whole["gnorm"] == pytest.approx(split["gnorm"], rel=1e-6)
    assert all(v > 0 for v in whole["change"].values())


def test_program_slices_are_named_by_every_stack_axis():
    norms = {("layers", "moe", "wg"): np.arange(6.0).reshape(2, 3),
             ("layers", "norm"): np.array([7.0, 8.0]),
             ("embed",): np.float32(9.0)}
    assert train_kind._named(norms) == {
        "layers.moe.wg.0.0": 0.0, "layers.moe.wg.0.1": 1.0,
        "layers.moe.wg.0.2": 2.0, "layers.moe.wg.1.0": 3.0,
        "layers.moe.wg.1.1": 4.0, "layers.moe.wg.1.2": 5.0,
        "layers.norm.0": 7.0, "layers.norm.1": 8.0, "embed": 9.0}


# --- a family added by files alone -------------------------------------------

NEW_FAMILY = '''"""The decoder's pieces under another name, with a scope of its
own."""
from pathlib import Path

from benchmarks.chip import bench

_decoder = bench.load_module(Path(__file__).with_name("decoder.py"))
globals().update({k: v for k, v in vars(_decoder).items()
                  if not k.startswith("__")})
EXTRA_SCOPES = ("experts",)
'''


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def _add_cell(root: Path, family: str, name: str) -> str:
    """A copy of the tiny GQA cell whose configuration names ``family``:
    a configuration file, a workload file and BENCHMARK.json entries."""
    bench_dir = root / "benchmarks" / "chip"
    conf = json.loads((bench_dir / "configs" / "tiny-gqa.json").read_text())
    conf.update(name=name, reference=family)
    (bench_dir / "configs" / f"{name}.json").write_text(json.dumps(conf))
    wl = json.loads((bench_dir / "workloads" / "tiny-gqa.train.json")
                    .read_text())
    wl["config"] = name
    cell = f"{name}.train"
    (bench_dir / "workloads" / f"{cell}.json").write_text(json.dumps(wl))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "tiny",
                            "file": f"benchmarks/chip/configs/{name}.json",
                            "reduced": [], "why": "tiny"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": "train", "chips": 1,
                              "why": "tiny"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


@pytest.fixture
def root(tmp_path, monkeypatch):
    import jax

    import chipbench_tiny as tiny

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield tiny.build(tmp_path / "tree")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def test_a_family_is_added_by_files(root, capsys):
    """A configuration naming a family that only the new ``refs/`` file
    defines runs to a correct result, with every other file of the harness
    as it is; its compared numbers are those of the family it copies."""
    import chipbench_tiny as tiny

    bench_dir = root / "benchmarks" / "chip"
    before = _digests(bench_dir)
    (bench_dir / "refs" / "copied_decoder.py").write_text(NEW_FAMILY)
    cell = _add_cell(root, "copied_decoder", "tiny-copied")
    added = set(_digests(bench_dir)) - set(before)
    assert added == {"refs/copied_decoder.py", "configs/tiny-copied.json",
                     "workloads/tiny-copied.train.json"}
    assert {k: v for k, v in _digests(bench_dir).items()
            if k not in added} == before
    rc, res = tiny.run(root, cell, 2**31 + 9, capsys)
    assert rc == 0 and res is not None
    assert res["correct"], res["checks"]
    assert model_lib.family(json.loads((bench_dir / "configs" /
                                        "tiny-copied.json").read_text()),
                            bench_dir).EXTRA_SCOPES == ("experts",)
    rc, same = tiny.run(root, "tiny-gqa.train", 2**31 + 9, capsys)
    assert rc == 0 and same["checks"] == res["checks"]


def test_an_absent_family_is_refused_by_name(root, capsys):
    from benchmarks.chip import run as run_mod

    cell = _add_cell(root, "nowhere", "tiny-nowhere")
    rc = run_mod.main(
        ["--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "0"],
        root=root, bench_dir=root / "benchmarks" / "chip",
        chip_check=lambda jax, chips: jax.devices()[:chips])
    out = capsys.readouterr()
    assert rc == 2 and "{" not in out.out
    assert "refs/nowhere.py" in out.err
    with pytest.raises(bench.BenchError, match=r"refs/nowhere\.py"):
        model_lib.param_shapes({"name": "x", "reference": "nowhere"},
                               root / "benchmarks" / "chip")


# --- a family's own scope names ----------------------------------------------

_OP = "jit(train_step)/transpose(jvp(decoder))/while/body/closed_call/mlp/"


@pytest.mark.parametrize("op_name,extra,want", [
    (_OP + "experts/dot_general", ("experts",), ("experts", "backward")),
    (_OP + "experts/dot_general", (), ("mlp", "backward")),
    (_OP + "dot_general", ("experts",), ("mlp", "backward")),
    ("jit(train_step)/jvp(experts)/rematted_computation/add", ("experts",),
     ("experts", "recompute")),
], ids=["inner_extra", "not_declared", "absent", "under_a_transform"])
def test_family_scope_is_attributed(op_name, extra, want):
    assert scopes.scope_of(op_name, extra) == want


def test_the_step_map_takes_the_family_scopes(root, monkeypatch):
    """The op map of a traced run attributes the scopes that the cell's
    family declares (compiling the step is stood in for by a text)."""
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "refs" / "copied_decoder.py").write_text(NEW_FAMILY)
    cell = _add_cell(root, "copied_decoder", "tiny-copied")
    found = bench.find_cell(root, cell, bench_dir)
    text = ("HloModule jit_train_step\n"
            '  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name="'
            + _OP + 'experts/dot_general"}\n'
            '  %fusion.2 = f32[8]{0} fusion(%fusion.1), metadata={op_name="'
            + _OP + 'dot_general"}\n')
    seen = []

    def compiled(conf, wl, where):
        seen.append((conf["name"], wl["kind"], where))
        return text

    monkeypatch.setattr(scopes, "compiled_step", compiled)
    rec = {"host": {}, "config": found.config, "workload": found.workload,
           "bench_dir": bench_dir}
    ops = scopes.step_op_map(rec)["ops"]
    assert seen == [("tiny-copied", "train", bench_dir)]
    assert ops["fusion.1"] == ("experts", "backward")
    assert ops["fusion.2"] == ("mlp", "backward")


# --- the generic files name no family ----------------------------------------


def test_generic_files_name_no_family_parameter_or_size():
    names = set()
    for conf in ("qwen1.5-4b-train", "minicpm3-4b-train"):
        for path, _ in model_lib.leaves(model_lib.param_shapes(_conf(conf))):
            names.update(path[1:] if path[0] == "layers" else ())
    names |= {"intermediate_size", "num_attention_heads",
              "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "head_dim", "qkv_bias"}
    for rel in GENERIC:
        text = (HARNESS / rel).read_text()
        found = sorted(n for n in names if re.search(rf"\b{n}\b", text))
        assert not found, (rel, found)
