"""Model FLOPs of a configuration, from its shapes alone.

PaLM's convention (Chowdhery et al. 2022, appendix B): 2 FLOPs per matmul
parameter per token forward and 6 forward + backward, the head counted
and the embedding lookup not; plus causal attention, where a token at
position p attends p + 1 keys and QK^T and PV cost 2 x (qk + v) FLOPs per
key per layer (times 3 with the backward); recomputation not counted.
"""

from __future__ import annotations

from benchmarks.chip.model import attention_kind, head_dim


def matmul_params(conf: dict) -> int:
    """Weights that multiply activations, per the published sizes: every
    layer's attention and MLP projections and the head over the published
    vocabulary (the program's padding of the vocabulary is not model
    work)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    if attention_kind(conf) == "mla":
        qr, kvr = conf["q_lora_rank"], conf["kv_lora_rank"]
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv = conf["v_head_dim"]
        attn = (d * qr + qr * H * (dn + dr) + d * kvr + d * dr
                + kvr * H * dn + kvr * H * dv + H * dv * d)
    else:
        hd, Hkv = head_dim(conf), conf["num_key_value_heads"]
        attn = 2 * d * H * hd + 2 * d * Hkv * hd
    return L * (attn + 3 * d * f) + d * conf["vocab_size"]


def attn_dims(conf: dict) -> tuple[int, int, int]:
    """(layers, heads * qk dim, heads * v dim)."""
    H = conf["num_attention_heads"]
    if attention_kind(conf) == "mla":
        qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        return conf["num_hidden_layers"], H * qk, H * conf["v_head_dim"]
    hd = head_dim(conf)
    return conf["num_hidden_layers"], H * hd, H * hd


def train_flops_per_token(conf: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per trained token: 6 per matmul
    parameter plus causal attention (a token at position p attends p + 1
    keys: QK^T and PV are 2 * (qk + v) FLOPs per key, times 3 for the
    backward)."""
    L, qk, v = attn_dims(conf)
    mean_keys = (seq_len + 1) / 2.0
    return 6.0 * matmul_params(conf) + 3.0 * 2.0 * L * (qk + v) * mean_keys

