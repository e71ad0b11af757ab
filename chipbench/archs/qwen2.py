"""Qwen2: a dense decoder of GQA attention with QKV bias and a gated
MLP, every layer alike, read from the configuration file's published
keys.  The architecture module of configurations whose ``reference``
is ``qwen2``: the program's ``ModelConfig``, the benchmark's weight
tree in the program's parameter layout, and the operations and bytes
the serving work needs.

The counts take the live context, never ``max_len``: a prefill of
``P`` tokens attends causally over ``P`` keys, a decode token at
context ``c`` attends over ``c`` keys, and the KV bytes a step needs
are those of the rows it reads.  Every parameter is active for every
token.

A multiply-add is 2 operations.  Weights and the KV cache are bfloat16
(2 bytes), the configuration's ``torch_dtype``.
"""
from __future__ import annotations

from typing import Dict, Iterable

from chipbench.flops import elt_bytes


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, kv, dh, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def program_config(name: str, cfg: dict):
    """The program's ``ModelConfig``, every size and constant taken from
    the file."""
    from repro.configs.base import AttentionCfg, ModelConfig
    d, h, kv, dh, f, v, n = dims(cfg)
    return ModelConfig(
        arch_id=name, family="dense", n_layers=n, d_model=d, d_ff=f,
        vocab=v,
        attention=AttentionCfg(n_heads=h, n_kv_heads=kv, d_head=dh,
                               qkv_bias=True,
                               rope_theta=float(cfg["rope_theta"])),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        act=cfg.get("hidden_act", "silu"),
        dtype=cfg.get("torch_dtype", "bfloat16"))


def weight_shapes(cfg: dict) -> Dict:
    """The tree of ``(shape, std)`` leaves: ``emb``, ``final_ln``,
    ``groups/b0/{ln1, attn/{wq,wk,wv,wo,bq,bk,bv}, ln2, ffn/{w1,w3,w2}}``
    stacked over layers, ``lm_head`` when untied.  RMSNorm gains are
    stored as offsets from 1.  Projections are normal with standard
    deviation ``fan_in**-0.5``; embedding and head 0.02; biases and norm
    offsets 0.1, so that every term of the forward pass moves the
    logits."""
    d, h, kv, dh, f, v, n = dims(cfg)
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
    if not cfg.get("tie_word_embeddings", False):
        tree["lm_head"] = ((v, d), 0.02)
    return tree


def layer_params(cfg: dict) -> int:
    d, h, kv, dh, f, _, _ = dims(cfg)
    qkv = d * (h + 2 * kv) * dh + (h + 2 * kv) * dh     # weights + bias
    return qkv + h * dh * d + 3 * d * f + 2 * d         # + o, mlp, norms


def params(cfg: dict) -> int:
    """All parameters; a tied embedding is counted once."""
    d, _, _, _, _, v, n = dims(cfg)
    heads = 1 if cfg.get("tie_word_embeddings", False) else 2
    return n * layer_params(cfg) + heads * v * d + d


def weight_bytes_per_step(cfg: dict, batch: int) -> int:
    """Weight bytes one forward step must read: every layer, the final
    norm and the whole output head once, plus ``batch`` embedding rows
    (a tied head is the embedding, read once as the head)."""
    d, _, _, _, _, v, n = dims(cfg)
    tied = cfg.get("tie_word_embeddings", False)
    rows = 0 if tied else batch * d
    return elt_bytes(cfg) * (n * layer_params(cfg) + v * d + d + rows)


def linear_flops_per_token(cfg: dict) -> int:
    """Projections and MLP of every layer for one token (no head)."""
    d, h, kv, dh, f, _, n = dims(cfg)
    return 2 * n * (d * (h + 2 * kv) * dh + h * dh * d + 3 * d * f)


def attn_flops(cfg: dict, n_keys: int) -> int:
    """Scores and weighted sum of one query token over ``n_keys`` keys,
    all layers."""
    _, h, _, dh, _, _, n = dims(cfg)
    return 4 * n * h * dh * n_keys


def head_flops(cfg: dict) -> int:
    d, _, _, _, _, v, _ = dims(cfg)
    return 2 * d * v


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """Prefill of one prompt: every token through every layer, causal
    attention over the keys before it, and next-token logits for the
    last position only (all that serving needs)."""
    p = int(prompt_len)
    causal_keys = p * (p + 1) // 2
    return p * linear_flops_per_token(cfg) + attn_flops(cfg, causal_keys) \
        + head_flops(cfg)


def decode_flops(cfg: dict, contexts: Iterable[int]) -> int:
    """One decode step: one token per active slot, each attending over
    its live context (its own new key included)."""
    total = 0
    for c in contexts:
        total += linear_flops_per_token(cfg) + attn_flops(cfg, int(c)) \
            + head_flops(cfg)
    return total


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one token position, all layers."""
    _, _, kv, dh, _, _, n = dims(cfg)
    return 2 * n * kv * dh * elt_bytes(cfg)


def decode_bytes(cfg: dict, contexts: Iterable[int]) -> int:
    """HBM bytes one decode step needs: the weights once, the live KV
    rows of every active slot read, and one new row written per slot."""
    contexts = [int(c) for c in contexts]
    kv = kv_row_bytes(cfg)
    return weight_bytes_per_step(cfg, len(contexts)) \
        + sum(c - 1 for c in contexts) * kv + len(contexts) * kv


def page_live_bytes(cfg: dict, prompt_len: int) -> int:
    """Bytes of one request's prefilled KV that a page copy must carry:
    the prompt's live rows (a per-layer length counter is noise)."""
    return int(prompt_len) * kv_row_bytes(cfg)
