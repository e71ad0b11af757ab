"""Seeded weights of a Qwen2-style configuration, made on the device in
one jitted call, in bfloat16 (the configuration's ``torch_dtype``).

The tree has the layout the serving program takes (``emb``,
``final_ln``, ``groups/b0/{ln1, attn/{wq,wk,wv,wo,bq,bk,bv}, ln2,
ffn/{w1,w3,w2}}`` stacked over layers, ``lm_head`` when untied), so the
same arrays feed the program and the reference.  RMSNorm gains are
stored as offsets from 1.  The benchmark makes them, not the program,
so the reference takes nothing the program has made.

Scales: projections are normal with standard deviation ``fan_in**-0.5``;
embedding and head 0.02; biases and norm offsets 0.1, so that every
term of the forward pass moves the logits.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops


def _dims(cfg: dict) -> Tuple:
    return flops.dims(cfg) + (bool(cfg.get("tie_word_embeddings", False)),)


def shapes(dims: Tuple) -> Dict:
    """The tree of ``(shape, std)`` pairs for ``_dims(cfg)``."""
    d, h, kv, dh, f, v, n, tied = dims
    tree = {
        "emb": ((v, d), 0.02),
        "final_ln": ((d,), 0.1),
        "groups": {"b0": {
            "ln1": ((n, d), 0.1),
            "ln2": ((n, d), 0.1),
            "attn": {
                "wq": ((n, d, h, dh), d ** -0.5),
                "wk": ((n, d, kv, dh), d ** -0.5),
                "wv": ((n, d, kv, dh), d ** -0.5),
                "wo": ((n, h, dh, d), (h * dh) ** -0.5),
                "bq": ((n, h, dh), 0.1),
                "bk": ((n, kv, dh), 0.1),
                "bv": ((n, kv, dh), 0.1),
            },
            "ffn": {
                "w1": ((n, d, f), d ** -0.5),
                "w3": ((n, d, f), d ** -0.5),
                "w2": ((n, f, d), f ** -0.5),
            },
        }},
    }
    if not tied:
        tree["lm_head"] = ((v, d), 0.02)
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@partial(jax.jit, static_argnames=("dims",))
def _init(seed_words, dims):
    leaves, treedef = jax.tree.flatten(shapes(dims), is_leaf=_is_leaf)
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
    return _init(jnp.asarray([lo, hi], jnp.uint32), _dims(cfg))


def abstract(cfg: dict):
    """Shapes and dtypes of ``init``'s tree, without making it."""
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.bfloat16),
        shapes(_dims(cfg)), is_leaf=_is_leaf)
