"""Pallas TPU flash attention (causal / sliding-window, GQA-native).

Grid: ``(batch, q_heads, nq, nk)`` with the KV-block dimension innermost and
sequential; the online-softmax accumulator lives in VMEM scratch across KV
steps (the canonical TPU flash pattern: MXU does the two matmuls per tile,
VPU the rescaling).  GQA is handled in the BlockSpec index map — the KV
block for q-head ``h`` is KV-head ``h // group`` — so KV tiles are fetched
once per KV head, preserving GQA's HBM-bandwidth saving (no repeat()).

Causal skipping: tiles with ``k0 > q0 + bq - 1`` contribute nothing and are
skipped via ``pl.when`` (compute and the output write are both predicated),
halving FLOPs at long S exactly like the unrolled jnp path.

VMEM budget per grid cell: q (bq, dh) + k,v (bk, dh) + acc (bq, dh) fp32 +
(m, l) — e.g. bq=bk=512, dh=128: ~0.9 MB, far under the ~128 MB/core VMEM;
block sizes are multiples of (8, 128) tiles for MXU alignment.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, nk: int, causal: bool,
                  window: Optional[int], scale: float,
                  logit_cap: Optional[float]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q0 = qi * bq
    k0 = ki * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # tile is live unless fully above the causal diagonal / below the window
    live = jnp.bool_(True)
    if causal:
        live &= k0 <= q0 + bq - 1
    if window is not None:
        live &= k0 + bk - 1 > q0 - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)
        rows = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        l_cur = jnp.sum(p, axis=1)
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + l_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "logit_cap",
                              "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    logit_cap: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B,S,H,dh); k,v: (B,S,KV,dh) -> (B,S,H,dh)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    assert H % KV == 0
    group = H // KV
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk

    # layout: (B, H, S, dh) so the head dim is a grid axis
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        scale=scale, logit_cap=logit_cap)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, i, j, group=group:
                         (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, i, j, group=group:
                         (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),   # acc
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
