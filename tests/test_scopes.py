"""The train step's layer scopes reach the compiled program's ``op_name``
metadata, where the benchmark reads each layer's device time from
(``benchmarks/chip/scopes.py``); and ``--profile-dir`` traces the loop's
host spans for the requested steps only."""

import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmarks.chip import scopes  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.api import get_optimizer  # noqa: E402
from repro.launch.steps import (TrainState, make_train_step,  # noqa: E402
                                make_warm_start)
from repro.launch.train import train  # noqa: E402
from repro.models.api import build_model  # noqa: E402

STEP_SCOPES = set(scopes.SCOPES) - {"warm_start"}


def _compiled(arch):
    """Compiled texts of the smoke model's warm start and plain train step
    (SubTrack++, kernels' path, full remat), from shapes."""
    bundle = build_model(get_config(arch, smoke=True))
    opt = get_optimizer("subtrack", rank=8, update_interval=200,
                        init="randomized", use_kernels=True)
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    state = TrainState(params=params, opt=jax.eval_shape(opt.init, params))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    warm = jax.jit(make_warm_start(bundle, opt, remat="full"))
    step = jax.jit(make_train_step(bundle, opt, remat="full"))
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    return (warm.lower(state, batch).compile().as_text(),
            step.lower(state, batch, lr).compile().as_text())


@pytest.fixture(scope="module", params=["qwen1.5-4b", "minicpm3-4b"])
def compiled(request):
    return _compiled(request.param)


def _named_ops(text, prefix):
    """(scope, pass) of every instruction whose op_name starts with
    ``prefix``."""
    out = []
    for line in text.splitlines():
        m, name = scopes._INSTR.match(line), scopes._OP_NAME.search(line)
        if m and name and name.group(1).startswith(prefix):
            out.append(scopes.scope_of(name.group(1)))
    return out


def test_every_scope_reaches_op_name(compiled):
    warm, step = compiled
    assert {s for s, _ in _named_ops(step, "jit(train_step)")} \
        >= STEP_SCOPES
    assert "warm_start" in {s for s, _ in _named_ops(warm, "jit(warm)")}
    assert "warm_start" not in {s for s, _ in scopes.op_map(step)[
        "ops"].values()}


def test_attention_and_mlp_show_every_pass(compiled):
    ops = set(_named_ops(compiled[1], "jit(train_step)"))
    for scope in ("attention", "mlp"):
        assert {p for s, p in ops if s == scope} == set(scopes.PASSES), scope


def test_nearly_every_step_instruction_is_scoped(compiled):
    ops = _named_ops(compiled[1], "jit(train_step)")
    scoped = sum(1 for s, _ in ops if s is not None)
    assert scoped >= 0.95 * len(ops), (scoped, len(ops))


def test_profile_traces_the_requested_steps(tmp_path):
    from jax.profiler import ProfileData

    train(["--arch", "llama-60m", "--smoke", "--batch", "2", "--seq", "32",
           "--rank", "8", "--steps", "6", "--log-every", "100",
           "--profile-dir", str(tmp_path), "--profile-steps", "2:4"])
    found = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    assert len(found) == 1, found
    names, steps = set(), []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "train":
                    steps.append(dict(ev.stats)["step_num"])
    assert {"dispatch", "drain", "fetch", "sentinel"} <= names
    assert sorted(steps) == [2, 3]


def test_profile_steps_must_be_a_range():
    with pytest.raises(SystemExit):
        train(["--smoke", "--profile-steps", "4:2"])
