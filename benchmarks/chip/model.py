"""A configuration file's model: its family, parameter layout and seeded
weights.

The configuration's ``reference`` key names its family,
``refs/<reference>.py`` in the harness's directory, which knows the
model's shape (``refs/decoder.py`` lists what a family gives).  Nothing
here names a parameter or a size: a later family is added by adding its
file.  The layout is the one the system under test consumes; it is
checked against the program's own shapes before a run, so a change of
schema fails loudly instead of feeding the program something else.  The
plain references in ``refs/`` read the same arrays.
"""

from __future__ import annotations

import functools
from pathlib import Path
from types import ModuleType

import numpy as np

from benchmarks.chip.bench import BENCH_DIR, BenchError, load_module


@functools.cache
def _load_family(path: Path) -> ModuleType:
    return load_module(path)


def family(conf: dict, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The family module that the configuration's ``reference`` names."""
    name = conf.get("reference")
    rel = f"refs/{name}.py"
    if not name or not (bench_dir / rel).is_file():
        raise BenchError(f"configuration {conf.get('name')!r} names the "
                         f"model family {name!r}, and there is no {rel} in "
                         f"{bench_dir}")
    return _load_family((bench_dir / rel).resolve())


def param_shapes(conf: dict, bench_dir: Path = BENCH_DIR) -> dict:
    """Nested dict of shapes in the program's layout."""
    return family(conf, bench_dir).param_shapes(conf)


def leaves(tree, prefix=()):
    """(path, leaf) pairs of a nested dict, in sorted key order."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from leaves(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def make_params(conf: dict, seed: int, bench_dir: Path = BENCH_DIR):
    """Every weight from ``seed``, in bfloat16, in one jitted call on the
    default device."""
    import jax
    import jax.numpy as jnp

    fam = family(conf, bench_dir)
    flat = list(leaves(fam.param_shapes(conf)))

    def build(key):
        out = {}
        for i, (path, shape) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32) * fam.init_scale(
                path, shape)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x.astype(jnp.bfloat16)
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed))


def check_layout(program_shapes, conf: dict,
                 bench_dir: Path = BENCH_DIR) -> None:
    """Raise when the program's parameter schema differs from ours."""
    ours = {p: tuple(s) for p, s in leaves(param_shapes(conf, bench_dir))}
    theirs = {p: tuple(s.shape) for p, s in leaves(program_shapes)}
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{diff[:6]}")


def seed32(seed: int) -> int:
    """Any whole number to a seed that numpy and jax both take."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)
