"""Jit'd public wrappers for all Pallas kernels.

``interpret`` defaults from the backend: compiled kernels on a TPU, the
Pallas interpreter on the CPU (the tests run there with
``JAX_PLATFORMS=cpu``).  The model stack routes through these when
``ModelConfig.use_pallas`` is set.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import page_install as _pi
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.page_install import (PageLayout, page_layout)  # noqa: F401
from repro.kernels.rg_lru import rg_lru_scan as _rg_lru
from repro.kernels.streamcopy import stream_copy as _stream_copy


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    logit_cap: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, scale=scale,
                  logit_cap=logit_cap, block_q=block_q, block_k=block_k,
                  interpret=interp)


def stream_copy(x, *, block_rows: int = 256, n_buffers: int = 2,
                interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _stream_copy(x, block_rows=block_rows, n_buffers=n_buffers,
                        interpret=interp)


def rg_lru_scan(a, b, h0=None, *, block_t: int = 64, block_w: int = 256,
                interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _rg_lru(a, b, h0, block_t=block_t, block_w=block_w,
                   interpret=interp)


def pack_page(layout, leaves, *, mode: str = "auto",
              interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _pi.pack_page(layout, leaves, mode=mode, interpret=interp)


def install_pages(layout, batch_leaves, pages, slots, *,
                  mode: str = "auto", n_buffers: int = 2,
                  interpret: Optional[bool] = None,
                  donate: bool = False, codec=None):
    interp = _default_interpret() if interpret is None else interpret
    return _pi.install_pages(layout, batch_leaves, pages, slots,
                             mode=mode, n_buffers=n_buffers,
                             interpret=interp, donate=donate,
                             codec=codec)


def install_slot(layout, batch_leaves, single_leaves, slot, *,
                 donate: bool = False):
    return _pi.install_slot(layout, batch_leaves, single_leaves, slot,
                            donate=donate)


# re-export oracles for test convenience
attention_ref = ref.attention_ref
stream_copy_ref = ref.stream_copy_ref
rg_lru_scan_ref = ref.rg_lru_scan_ref
pack_page_ref = _pi.pack_page_ref
install_pages_ref = _pi.install_pages_ref
