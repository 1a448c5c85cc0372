"""The harness end to end on the CPU with tiny training cells that are
defined only here (one GQA, one MLA): a sound run is correct and reports
the cell's end-to-end metrics."""

from __future__ import annotations

import jax
import pytest

import chipbench_tiny as tiny


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield tiny.build(tmp_path / "tree")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


@pytest.mark.parametrize("cell", ["tiny-gqa.train", "tiny-mla.train"])
def test_sound_run_is_correct(root, capsys, cell):
    rc, res = tiny.run(root, cell, 2**31 + 7, capsys)
    assert rc == 0 and res is not None
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"train_tokens_per_s", "peak_hbm_gib", "setup_s"} <= set(
        res["metrics"])
    assert res["device"]["platform"] == "cpu"
