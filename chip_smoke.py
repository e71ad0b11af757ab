#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip.

Serves qwen2-0.5b at its published widths (random weights from seed 0)
through ``repro.launch.serve.main`` three times in this one process:
without KV paging, with paging through the fused Pallas page kernels,
and with paging through the per-leaf reference chain.  It fails unless
all three decode the same tokens, every request is served, and the
fused run installed every page through the kernels.

    python3 chip_smoke.py

The earlier lines report each run (tokens, wall seconds, TTFT/TPOT
medians, compile seconds); they are for reading, not a benchmark.  The
last line is the JSON verdict, printed only when every check passed.  A
host without a TPU, or a directory without the repo's ``src/``, exits
non-zero before serving anything.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "qwen2-0.5b"
SERVE = ["--arch", ARCH, "--requests", "8", "--slots", "4",
         "--prompt-len", "128", "--max-new", "32", "--max-len", "512"]
RUNS = {
    "no_paging": [],
    "fused": ["--kv-paging", "--access-path", "xdma"],
    "per_leaf": ["--kv-paging", "--access-path", "xdma",
                 "--no-fused-install"],
}


def fail(msg: str) -> int:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def check_run(name: str, res: dict, vocab: int, n_requests: int) -> str:
    """Empty string when the run is sound, else what is wrong."""
    outs = res["outputs"]
    if res["requests"] != n_requests or len(outs) != n_requests:
        return f"{name}: served {res['requests']} of {n_requests}"
    if res["undrained"] != 0:
        return f"{name}: {res['undrained']} requests undrained"
    if not all(out for out in outs.values()):
        return f"{name}: a request decoded no token"
    bad = [t for out in outs.values() for t in out if not 0 <= t < vocab]
    if bad:
        return f"{name}: token ids outside [0, {vocab}): {bad[:4]}"
    inst = res["install"]
    if name == "fused" and (inst["fused"] <= 0 or inst["fallback"] != 0):
        return f"{name}: install counters {inst}"
    if name == "per_leaf" and (inst["fallback"] <= 0 or inst["fused"] != 0):
        return f"{name}: install counters {inst}"
    return ""


def main() -> int:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return fail(f"no repro package under {SRC}")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        return fail(f"JAX found no TPU (device 0 is {dev.platform})")
    print(f"[chip_smoke] device_kind={dev.device_kind} count={len(devs)} "
          f"compile_cache={cache}", flush=True)

    from repro.configs import get_config
    from repro.launch import serve
    vocab = get_config(ARCH).vocab
    n_requests = int(SERVE[SERVE.index("--requests") + 1])

    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    outputs = {}
    for name, extra in RUNS.items():
        compile_s[0] = 0.0
        t0 = time.perf_counter()
        res = serve.main(SERVE + extra)
        wall = time.perf_counter() - t0
        lat = res["latency"]
        print(f"[chip_smoke] run={name} requests={res['requests']} "
              f"tokens={res['tokens']} wall_s={wall} "
              f"ttft_p50_s={lat['ttft_s']['p50']} "
              f"tpot_p50_s={lat['tpot_s']['p50']} "
              f"compile_s={compile_s[0]} install={res['install']}",
              flush=True)
        why = check_run(name, res, vocab, n_requests)
        if why:
            return fail(why)
        outputs[name] = res["outputs"]
    jax.monitoring.unregister_event_duration_listener(on_duration)

    ref = outputs["no_paging"]
    for name, outs in outputs.items():
        if outs != ref:
            diff = [r for r in ref if outs.get(r) != ref[r]]
            return fail(f"{name} outputs differ from no_paging at "
                        f"requests {diff}")
    print(f"[chip_smoke] parity: {len(RUNS)} runs decoded identical "
          f"tokens for {len(ref)} requests", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
