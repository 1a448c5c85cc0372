"""A throwaway benchmark tree for the CPU tests: a copy of this harness
plus tiny cells that exist only here, added as files and entries the way a
later change would add them."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HARNESS = Path(__file__).resolve().parents[1]
REPO = HARNESS.parents[1]

OPT = {"rank": 16, "update_interval": 200, "eta": 10.0, "lr": 0.001,
       "beta1": 0.9, "beta2": 0.999, "eps": 1e-08, "scale": 0.25,
       "zeta": 1.01, "clip_norm": 1.0,
       "init": {"method": "randomized", "seed": 0, "oversample": 8,
                "power_iters": 2}}

# Limits of the tiny cells, set from their readings on the CPU.  Program
# against the float32 reference (GQA and MLA, 6 to 20 seeds): first-step
# loss gap at most 0.0019, first-gradient gap 0.047, change gap 0.074.
# The float8 control (seeds 0-2 of each): loss gap 0.003-0.025,
# first-gradient gap 0.046-0.068, change gap 0.018-0.059; half of the
# batch left out: first-gradient gap 0.16-0.36, change gap 0.22-0.53.
TRAIN_LIMITS = {"loss_gap": 0.0025, "grad_gap": 0.1, "change_gap": 0.15}


def _conf(name, **kw):
    base = {"name": name, "source": "tiny", "hidden_size": 128,
            "intermediate_size": 256, "num_attention_heads": 4,
            "num_key_value_heads": 4, "num_hidden_layers": 2,
            "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
            "tie_word_embeddings": False, "reduced": [], "assumed": [],
            "reference": "decoder"}
    base.update(kw)
    return base


def build(tmp: Path) -> Path:
    """Returns the root of a checkout-like tree holding the tiny cells."""
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(HARNESS, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "src").symlink_to(REPO / "src")
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 2**34}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    confs = {
        "tiny-gqa": _conf("tiny-gqa", program={
            "arch": "qwen1.5-4b", "smoke": True, "qkv_bias": True,
            "vocab_round": 64}),
        "tiny-mla": _conf("tiny-mla", rms_norm_eps=1e-5, q_lora_rank=48,
                          kv_lora_rank=32, qk_nope_head_dim=16,
                          qk_rope_head_dim=16, v_head_dim=32, program={
                              "arch": "minicpm3-4b", "smoke": True,
                              "vocab_round": 64}),
    }
    for name, conf in confs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(conf))
    cells = {
        "tiny-gqa.train": {"config": "tiny-gqa", "kind": "train",
                           "seq_len": 32, "batch": 2, "accum": 1,
                           "remat": "full", "optimizer": OPT,
                           "limits": TRAIN_LIMITS, "why": "tiny"},
        "tiny-mla.train": {"config": "tiny-mla", "kind": "train",
                           "seq_len": 32, "batch": 2, "accum": 1,
                           "remat": "full", "optimizer": OPT,
                           "limits": TRAIN_LIMITS, "why": "tiny"},
    }
    for name, wl in cells.items():
        (bench / "workloads" / f"{name}.json").write_text(json.dumps(wl))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] += [{"name": n, "source": "tiny",
                         "file": f"benchmarks/chip/configs/{n}.json",
                         "reduced": [], "why": "tiny"} for n in confs]
    spec["workloads"] += [{"name": n, "config": wl["config"],
                           "traffic": n.split(".")[1], "chips": 1,
                           "why": "tiny"} for n, wl in cells.items()]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += list(cells)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, workload: str, seed: int, capsys, trace: int = 0):
    """Drive ``run.main`` for a tiny cell on the CPU; returns (rc, result
    dict or None)."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.chip import run as run_mod

    rc = run_mod.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace)], root=root,
        bench_dir=root / "benchmarks" / "chip",
        chip_check=lambda jax, chips: jax.devices()[:chips])
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1]) if out and out[-1].startswith("{") else None
    return rc, last
