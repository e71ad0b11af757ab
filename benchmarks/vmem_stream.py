"""Paper Fig 8: on-chip (BRAM -> VMEM) tier bandwidth vs transfer size.

Runs the Pallas streamcopy kernel (compiled on a TPU, interpreted on the
CPU) and reports the *modeled* TPU HBM<->VMEM pipeline
bandwidth per (block size x buffer count), plus the paper-path projection.
Derived column shows modeled bandwidth: with n buffers the pipeline hides
min(n-1, 1) of the two DMA legs — the same multi-channel aggregation the
paper measures on BRAM (single channel ~7.5 GB/s of a 15.8 GB/s link).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, time_call
from repro.core.analytical import bandwidth_gbps, paper_pcie_bram
from repro.core.channels import Direction
from repro.core.tiers import TPU_V5E
from repro.kernels import ops

BLOCK_ROWS = [8, 32, 128]
BUFFERS = [1, 2, 4]
COLS = 512


def modeled_vmem_gbps(block_bytes: int, n_buffers: int) -> float:
    """Two DMA legs/block; >=2 buffers overlap them; deeper helps latency."""
    hbm = TPU_V5E["hbm"].bw_gbps * 1e9
    lat = 1e-6                                     # per-DMA issue latency
    t_leg = block_bytes / hbm + lat
    legs = 2.0 if n_buffers == 1 else (1.0 + 1.0 / n_buffers)
    return block_bytes / (legs * t_leg) / 1e9


def run(quick: bool = False) -> None:
    rows_total = 256 if quick else 512
    bram = paper_pcie_bram()
    for br in (BLOCK_ROWS[:2] if quick else BLOCK_ROWS):
        for nb in (BUFFERS[:2] if quick else BUFFERS):
            x = jnp.asarray(np.random.default_rng(0).standard_normal(
                (rows_total, COLS)), jnp.float32)
            fn = lambda: ops.stream_copy(
                x, block_rows=br, n_buffers=nb).block_until_ready()
            t = time_call(fn, repeats=2, warmup=1)
            block_bytes = br * COLS * 4
            modeled = modeled_vmem_gbps(block_bytes, nb)
            paper_bw = bandwidth_gbps(bram, block_bytes, nb, Direction.C2H)
            emit(f"fig8_vmem_block{br}x{COLS}_buf{nb}", t * 1e6,
                 f"block={block_bytes>>10}KB modeled_tpu={modeled:.0f}GB/s "
                 f"paper_bram={paper_bw:.1f}GB/s")


if __name__ == "__main__":
    run()
