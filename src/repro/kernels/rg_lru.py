"""Pallas TPU kernel for the RG-LRU diagonal linear recurrence.

    h_t = a_t * h_{t-1} + b_t        (elementwise over the width dim)

Grid: ``(B, nw, nt)`` — batch and width are parallel; the time dimension is
innermost/sequential with the carry ``h`` held in VMEM scratch across time
blocks.  Within a block the recurrence runs as a VPU loop over ``bt`` steps
on (8-sublane x bw-lane) registers; the op is HBM-bandwidth-bound (3 reads
+ 1 write per element), so the serial inner loop costs nothing once tiles
are resident — the same blocking RecurrentGemma's production scan uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rg_lru_kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref, *, bt: int):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        carry_ref[...] = h0_ref[0]

    h = carry_ref[...]
    a = a_ref[0]
    b = b_ref[0]
    out = jnp.zeros_like(a)
    for t in range(bt):            # static unroll: VPU fma chain
        h = a[t] * h + b[t]
        out = out.at[t].set(h)
    o_ref[0] = out
    carry_ref[...] = h


@functools.partial(jax.jit, static_argnames=("block_t", "block_w",
                                             "interpret"))
def rg_lru_scan(a: jax.Array, b: jax.Array, h0: jax.Array = None, *,
                block_t: int = 64, block_w: int = 256,
                interpret: bool = False) -> jax.Array:
    """a, b: (B, T, W) fp32; h0: (B, W) -> h: (B, T, W)."""
    B, T, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), a.dtype)
    bt = min(block_t, T)
    bw = min(block_w, W)
    assert T % bt == 0 and W % bw == 0
    nt, nw = T // bt, W // bw

    kernel = functools.partial(_rg_lru_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, bw), lambda bi, wi, ti: (bi, wi)),
        ],
        out_specs=pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((B, T, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((bw,), a.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
