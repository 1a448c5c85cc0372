"""A configuration file's model: its parameter layout and seeded weights.

The layout is the one the system under test consumes (stacked layers,
``(in, out)`` matrices, RMSNorm scales stored as offsets from 1); it is
written out here from the configuration's published sizes and checked
against the program's own shapes before a run, so a change of schema
fails loudly instead of feeding the program something else.  The plain
references in ``refs/`` read the same arrays.
"""

from __future__ import annotations

import math

import numpy as np


def padded_vocab(conf: dict) -> int:
    step = int(conf["program"].get("vocab_round", 256))
    return -(-conf["vocab_size"] // step) * step


def attention_kind(conf: dict) -> str:
    return "mla" if "kv_lora_rank" in conf else "gqa"


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf[
        "num_attention_heads"]


def param_shapes(conf: dict) -> dict:
    """Nested dict of shapes in the program's layout."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    V = padded_vocab(conf)
    if attention_kind(conf) == "mla":
        qr, kvr = conf["q_lora_rank"], conf["kv_lora_rank"]
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv = conf["v_head_dim"]
        attn = {"w_dq": (d, qr), "q_norm": (qr,), "w_uq": (qr, H * (dn + dr)),
                "w_dkv": (d, kvr), "kv_norm": (kvr,), "w_kr": (d, dr),
                "w_uk": (kvr, H, dn), "w_uv": (kvr, H, dv),
                "wo": (H * dv, d)}
    else:
        hd, Hkv = head_dim(conf), conf["num_key_value_heads"]
        attn = {"wq": (d, H * hd), "wk": (d, Hkv * hd), "wv": (d, Hkv * hd),
                "wo": (H * hd, d)}
        if conf["program"].get("qkv_bias"):
            attn.update(bq=(H * hd,), bk=(Hkv * hd,), bv=(Hkv * hd,))
    layer = {"ln1": (d,), "ln2": (d,), "attn": attn,
             "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}
    stack = lambda s: (L,) + s  # noqa: E731
    shapes = {"embed": (V, d),
              "layers": _map(stack, layer),
              "final_norm": (d,)}
    if not conf.get("tie_word_embeddings", False):
        shapes["lm_head"] = (d, V)
    return shapes


def _map(fn, tree):
    if isinstance(tree, dict):
        return {k: _map(fn, v) for k, v in tree.items()}
    return fn(tree)


def leaves(tree, prefix=()):
    """(path, leaf) pairs of a nested dict, in sorted key order."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from leaves(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def _init_scale(path: tuple, shape: tuple) -> float:
    name = path[-1]
    if name in ("ln1", "ln2", "final_norm", "q_norm", "kv_norm"):
        return 0.05            # offsets of the RMSNorm gains from 1
    if name in ("bq", "bk", "bv"):
        return 0.1
    if name == "embed":
        return 0.02
    fan_in = shape[-3] if name in ("w_uk", "w_uv") else shape[-2]
    return 1.0 / math.sqrt(fan_in)


def make_params(conf: dict, seed: int):
    """Every weight from ``seed``, in bfloat16, in one jitted call on the
    default device."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(conf)
    flat = list(leaves(shapes))

    def build(key):
        out = {}
        for i, (path, shape) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32) * _init_scale(
                path, shape)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x.astype(jnp.bfloat16)
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed))


def check_layout(program_shapes, conf: dict) -> None:
    """Raise when the program's parameter schema differs from ours."""
    ours = {p: tuple(s) for p, s in leaves(param_shapes(conf))}
    theirs = {p: tuple(s.shape) for p, s in leaves(program_shapes)}
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{diff[:6]}")


def seed32(seed: int) -> int:
    """Any whole number to a seed that numpy and jax both take."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)
