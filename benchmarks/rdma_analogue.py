"""Paper Figs 19-20 (RDMA read/write): the device<->device ICI path.

RDMA on the SoC SmartNIC is the 'easy API on a separate link' path; on a
TPU host that's the ICI device<->device transfer.  This bench runs in
the calling process on the devices present — a chip belongs to one
process, so it never starts a child — and times jax.device_put from
device 0 to device 1 (write analogue) and back (read analogue),
projecting onto the ICI model.  With fewer than two devices it says it
cannot run and reports no number.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit


def _median_put(x, dev, repeats: int = 3) -> tuple:
    ts, y = [], None
    for _ in range(repeats):
        t0 = time.perf_counter()
        y = jax.device_put(x, dev)
        y.block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), y


def run(quick: bool = False) -> None:
    devs = jax.devices()
    if len(devs) < 2:
        print(f"# fig19_20_rdma: cannot run, needs 2 devices and found "
              f"{len(devs)} ({devs[0].platform})", flush=True)
        return
    sizes = [1 << 18, 1 << 20] if quick else [1 << 18, 1 << 20, 1 << 22]
    from repro.core.analytical import bandwidth_gbps, tpu_ici_path
    from repro.core.channels import Direction
    ici = tpu_ici_path()
    d0, d1 = devs[0], devs[1]
    for size in sizes:
        x = jax.device_put(jnp.zeros((size // 4,), jnp.float32), d0)
        x.block_until_ready()
        t_w, y = _median_put(x, d1)
        t_r, _ = _median_put(y, d0)
        for op, t in (("write", t_w), ("read", t_r)):
            proj = bandwidth_gbps(ici, size, 1, Direction.C2H)
            emit(f"fig19_20_rdma_{op}_{size >> 10}KB", t * 1e6,
                 f"meas={size/t/1e9:.2f}GB/s ici_model={proj:.1f}GB/s "
                 f"device={d0.platform}:{d0.device_kind}")


if __name__ == "__main__":
    run()
