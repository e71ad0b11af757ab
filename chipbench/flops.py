"""Operations and bytes that the serving work *needs*, from shapes alone.

Each count is the architecture module's that the configuration names
(``chipbench/archs/<reference>.py``), which knows its layer kinds: what
a window layer's rows or an expert layer's active weights cost.  Every
count takes live lengths (a prompt's length, each active slot's live
context), never ``max_len``, so a later change that stops reading dead
rows raises a roofline share and leaves these counts as they are.

A multiply-add is 2 operations.
"""
from __future__ import annotations

from typing import Iterable

from chipbench import spec

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def elt_bytes(cfg: dict) -> int:
    """Bytes of one element of the configuration's ``torch_dtype``."""
    return BYTES[cfg.get("torch_dtype", "bfloat16")]


def _arch(cfg: dict):
    return spec.arch(cfg["reference"])


def params(cfg: dict) -> int:
    """All parameters stored on the chip."""
    return _arch(cfg).params(cfg)


def layer_params(cfg: dict) -> int:
    """Parameters of one layer, in a family whose layers are alike."""
    return _arch(cfg).layer_params(cfg)


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """Prefill of one prompt of ``prompt_len`` tokens."""
    return _arch(cfg).prefill_flops(cfg, prompt_len)


def decode_flops(cfg: dict, contexts: Iterable[int]) -> int:
    """One decode step: one token per active slot at its live context."""
    return _arch(cfg).decode_flops(cfg, contexts)


def kv_row_bytes(cfg: dict) -> int:
    """KV bytes of one token position, in a family that keeps one row of
    every layer per position."""
    return _arch(cfg).kv_row_bytes(cfg)


def decode_bytes(cfg: dict, contexts: Iterable[int]) -> int:
    """HBM bytes one decode step needs at the slots' live contexts."""
    return _arch(cfg).decode_bytes(cfg, contexts)


def page_live_bytes(cfg: dict, prompt_len: int) -> int:
    """Bytes of one request's prefilled state that a page copy must
    carry."""
    return _arch(cfg).page_live_bytes(cfg, prompt_len)
