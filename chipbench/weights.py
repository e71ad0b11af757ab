"""Seeded weights of a configuration, made on the device in one jitted
call, in bfloat16 (the configuration's ``torch_dtype``).

The tree of ``(shape, std)`` leaves is the architecture module's
(``chipbench/archs/<reference>.py``, ``weight_shapes``), in the layout
the serving program takes, so the same arrays feed the program and the
reference.  Each leaf is a normal draw scaled by its ``std``, from one
key per leaf split off the seed in flatten order.  The benchmark makes
the weights, not the program, so the reference takes nothing the
program has made.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _flat_shapes(cfg: dict):
    tree = spec.arch(cfg["reference"]).weight_shapes(cfg)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    return tuple(leaves), treedef


@partial(jax.jit, static_argnames=("leaves", "treedef"))
def _init(seed_words, leaves, treedef):
    key = jax.random.fold_in(jax.random.PRNGKey(seed_words[0]),
                             seed_words[1])
    keys = jax.random.split(key, len(leaves))
    out = [(std * jax.random.normal(k, shape, jnp.float32)
            ).astype(jnp.bfloat16)
           for k, (shape, std) in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    seed = int(seed)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def init(cfg: dict, seed: int):
    """All weights of ``cfg`` from ``seed``, on the default device."""
    lo, hi = seed_words(seed)
    leaves, treedef = _flat_shapes(cfg)
    return _init(jnp.asarray([lo, hi], jnp.uint32), leaves=leaves,
                 treedef=treedef)


def abstract(cfg: dict):
    """Shapes and dtypes of ``init``'s tree, without making it."""
    leaves, treedef = _flat_shapes(cfg)
    return jax.tree.unflatten(
        treedef, [jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                  for shape, _ in leaves])
