"""Operations and bytes that the serving work *needs*, from shapes alone.

All counts are of a dense decoder (Qwen2-style: GQA attention with QKV
bias, gated MLP) read from the configuration file's published keys.
They count the live context, never ``max_len``: a prefill of ``P``
tokens attends causally over ``P`` keys, a decode token at context
``c`` attends over ``c`` keys, and the KV bytes a step needs are those
of the rows it reads.  So a later change that stops reading dead rows
raises a roofline share and leaves these counts as they are.

A multiply-add is 2 operations.  Weights and the KV cache are bfloat16
(2 bytes), the configuration's ``torch_dtype``.
"""
from __future__ import annotations

from typing import Iterable

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, kv, dh, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def elt_bytes(cfg: dict) -> int:
    return BYTES[cfg.get("torch_dtype", "bfloat16")]


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
