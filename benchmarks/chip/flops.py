"""Model FLOPs of a configuration, from its shapes alone.

PaLM's convention (Chowdhery et al. 2022, appendix B): 2 FLOPs per matmul
parameter per token forward and 6 forward + backward, the head counted
and the embedding lookup not; plus causal attention, where a token at
position p attends p + 1 keys and QK^T and PV cost 2 x (qk + v) FLOPs per
key per layer (times 3 with the backward); recomputation not counted.
The sizes come from the configuration's family (``model.family``).
"""

from __future__ import annotations

from pathlib import Path

from benchmarks.chip.bench import BENCH_DIR
from benchmarks.chip.model import family


def matmul_params(conf: dict, bench_dir: Path = BENCH_DIR) -> int:
    """Weights that multiply one token's activations, per the published
    sizes, the head included."""
    return family(conf, bench_dir).matmul_params(conf)


def attn_dims(conf: dict, bench_dir: Path = BENCH_DIR) -> tuple[int, int,
                                                                int]:
    """(attention layers, heads * qk dim, heads * v dim)."""
    return family(conf, bench_dir).attn_dims(conf)


def train_flops_per_token(conf: dict, seq_len: int,
                          bench_dir: Path = BENCH_DIR) -> float:
    """Forward + backward model FLOPs per trained token: 6 per matmul
    parameter plus causal attention (a token at position p attends p + 1
    keys: QK^T and PV are 2 * (qk + v) FLOPs per key, times 3 for the
    backward)."""
    L, qk, v = attn_dims(conf, bench_dir)
    mean_keys = (seq_len + 1) / 2.0
    return 6.0 * matmul_params(conf, bench_dir) + 3.0 * 2.0 * L * (
        qk + v) * mean_keys
