"""CPU tests of the benchmark's yardstick: the trace reduction (on a trace
recorded on a TPU v5e and kept here), the FLOP counts against a hand count,
the optimizer kernels' names, and the run command's refusal to run without
a TPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[2]
sys.path.insert(0, str(REPO))

from benchmarks.chip import bench, flops  # noqa: E402
from benchmarks.chip import trace as trace_lib  # noqa: E402

SMALL_TRACE = HERE / "data" / "small.xplane.pb"


def _conf(name):
    return json.loads((REPO / "benchmarks/chip/configs" / f"{name}.json")
                      .read_text())


# --- trace reduction --------------------------------------------------------


def test_busy_and_gaps_of_a_hand_made_trace():
    tr = trace_lib.Trace(
        window=(0.0, 10.0),
        ops=[[(1.0, 3.0, "a"), (2.0, 4.0, "b"), (6.0, 7.0, "loop"),
              (6.2, 6.9, "a")]],
        modules=[[(0.9, 4.0, "step")]],
        spans=[(0.0, 5.0, "dispatch"), (4.5, 6.0, "drain")])
    assert trace_lib.merged(tr.ops[0]) == [(1.0, 4.0), (6.0, 7.0)]
    assert trace_lib.busy_s(tr) == pytest.approx(4.1)
    assert trace_lib.op_seconds(tr, lambda n: n == "a") == pytest.approx(2.7)
    # the loop holds an op, so it is left out of the top list
    assert trace_lib.top_ops(tr) == [["a", pytest.approx(2.7)], ["b", 2.0]]
    # gaps: [0, 0.9] under dispatch, [4, 6] mid 5 under drain (the
    # innermost span), [7, 10] under no span
    assert dict(map(tuple, trace_lib.idle_gaps(tr))) == pytest.approx(
        {"dispatch": 0.9, "drain": 2.0, "no span": 3.0})


def test_reduction_of_a_recorded_tpu_trace():
    tr = trace_lib.load(str(SMALL_TRACE))
    assert tr.ops and all(tr.ops), "no device ops in the recorded trace"
    busy = trace_lib.busy_s(tr)
    assert 0.0 < busy <= tr.window_s
    names = [n for n, _ in trace_lib.top_ops(tr)]
    assert any("project_colnorms" in n for n in names), names
    gaps = dict(map(tuple, trace_lib.idle_gaps(tr)))
    assert sum(gaps.values()) == pytest.approx(tr.window_s - busy, rel=1e-6)
    for d in tr.ops:
        assert all(tr.window[0] <= s <= e <= tr.window[1] for s, e, _ in d)


# --- FLOPs ------------------------------------------------------------------


def test_flops_match_a_hand_count_for_qwen():
    c = _conf("qwen1.5-4b-train")
    d, f, L, V = 2560, 6912, 13, 151936
    per_layer = 4 * d * d + 3 * d * f          # q, k, v, o; gate, up, down
    params = L * per_layer + d * V             # plus the untied head
    assert flops.matmul_params(c) == params == 1_419_837_440
    S = 2048
    attn = 6 * L * (2 * d) * (S + 1) / 2       # QK^T and PV, causal, x3
    assert flops.train_flops_per_token(c, S) == pytest.approx(
        6 * params + attn)


def test_flops_count_the_latent_attention_projections():
    c = _conf("minicpm3-4b-train")
    d, H = 2560, 40
    attn = (d * 768 + 768 * H * 96 + d * 256 + d * 32 + 256 * H * 64
            + 256 * H * 64 + H * 64 * d)
    L = c["num_hidden_layers"]
    assert flops.matmul_params(c) == L * (attn + 3 * d * 6400) + d * 73448


# --- the optimizer kernels' names --------------------------------------------

OPT_MS = bench.load_module(REPO / "benchmarks/chip/metrics"
                           / "opt_kernel_ms.train.py")
_BUCKET = ('%vmap__.11 = bf16[2,2560,152064]{2,1,0} custom-call(bf16[2,2560,'
           '152064]{2,1,0} %p, f32[2,2560,512]{2,1,0} %s), custom_call_'
           'target="tpu_custom_call"')
_ATTN = ('%flash_fwd.3 = bf16[1,20,2048,128]{3,2,1,0} custom-call(bf16[1,20,'
         '2048,128]{3,2,1,0} %q), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("name,rank,counted", [
    (_BUCKET, 512, True),
    (_BUCKET, 256, False),          # no float32 array of this rank
    (_ATTN, 512, False),            # a kernel outside the optimizer's list
    (_BUCKET.replace("tpu_custom_call", "AllocateBuffer"), 512, False),
], ids=["stacked_bucket", "other_rank", "attention_kernel", "not_a_kernel"])
def test_optimizer_kernels_are_named(name, rank, counted):
    assert OPT_MS.is_optimizer_kernel(name, rank) is counted


def test_other_kernels_are_left_out_and_named(capsys):
    tr = trace_lib.Trace(window=(0.0, 1.0),
                         ops=[[(0.0, 0.25, _BUCKET), (0.5, 0.6, _ATTN)]],
                         modules=[[]], spans=[])
    assert OPT_MS.kernel_seconds(tr, 512) == pytest.approx(0.25)
    assert "%flash_fwd.3 tpu_custom_call" in capsys.readouterr().err


def test_recorded_kernel_is_the_optimizers():
    tr = trace_lib.load(str(SMALL_TRACE))
    # the recorded run projected onto a rank-128 basis
    assert OPT_MS.kernel_seconds(tr, 128) == pytest.approx(
        trace_lib.op_seconds(tr, lambda n: "project_colnorms" in n))
    assert OPT_MS.kernel_seconds(tr, 128) > 0


# --- memory -------------------------------------------------------------------


class _Dev:
    def __init__(self, used, held):
        self.stats = {"bytes_in_use": used, "bytes_reserved": held,
                      "peak_bytes_in_use": 10 * used}

    def memory_stats(self):
        return self.stats


def test_footprint_is_one_instant_of_the_fullest_chip():
    from benchmarks.chip import run as run_mod

    r = run_mod.Run.__new__(run_mod.Run)
    r.devices, r.memory_samples = [_Dev(5, 1), _Dev(3, 4)], {}
    r.sample_memory("after set-up")
    r.devices = [_Dev(2, 2), _Dev(6, 0)]
    r.sample_memory("after the window")
    r.devices = [_Dev(50, 50)]
    r.sample_memory("after the warm start")   # logged, not the metric
    assert r.memory_samples["after set-up"] == 7
    assert r.hbm_footprint_bytes() == 7


# --- the run command --------------------------------------------------------


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen1.5-4b.train-2k", "--seed", str(2**31 + 1), "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
