"""Every fault a training cell can have, planted in the timed path of a
tiny cell defined only here, makes ``correct`` false; and the control (the
reference in float8 put in the program's place) fails the cell's limits.
On one chip there is no exchange between chips to leave out."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield tiny.build(tmp_path / "tree")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def _broken_step(monkeypatch, wrap):
    """Replace the program's train-step factory so the timed path runs
    ``wrap(step)`` instead of the step."""
    from repro.launch import steps

    real = steps.make_train_step

    def factory(*a, **kw):
        return wrap(real(*a, **kw))

    monkeypatch.setattr(steps, "make_train_step", factory)


def _unchanged(step):
    def f(state, batch, lr, **kw):
        _, metrics = step(state, batch, lr, **kw)
        return state, metrics
    return f


def _half_batch(step):
    """Half of the rows left out, the mean taken over the rest (the kept
    rows stand twice, so the shapes, and the compiled programs, stay)."""
    def f(state, batch, lr, **kw):
        half = batch["tokens"].shape[0] // 2
        keep = batch["tokens"][:half]
        return step(state, {"tokens": jnp.concatenate([keep, keep])}, lr,
                    **kw)
    return f


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(root, capsys, monkeypatch, fault):
    _broken_step(monkeypatch, fault)
    rc, res = tiny.run(root, "tiny-gqa.train", 11, capsys)
    assert rc == 0 and res is not None
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float8_control_fails_the_limits(root, seed):
    import json

    from benchmarks.chip.kinds import train as train_kind

    conf = json.loads((root / "benchmarks/chip/configs/tiny-gqa.json")
                      .read_text())
    wl = json.loads((root / "benchmarks/chip/workloads/tiny-gqa.train.json")
                    .read_text())
    batch = train_kind.Program(conf, wl).data(seed)
    exact = train_kind.reference_readings(conf, wl, seed, batch)
    low = train_kind.reference_readings(conf, wl, seed, batch, fmt="fp8")
    c = train_kind.compare(low, exact)
    assert any(c[k] > wl["limits"][k] for k in wl["limits"]), c
