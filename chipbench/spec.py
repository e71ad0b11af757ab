"""Resolve a cell by name: ``BENCHMARK.json`` entry, cell file, model
configuration, traffic mix, per-layer metric readers and device peaks.

Every piece lives in a file of its own under ``chipbench/`` and is found
from the name ``BENCHMARK.json`` gives it, so a new cell, configuration,
mix or metric is new files and entries, never an edit.  A configuration's
``reference`` names its family: the plain reference
(``references/<name>.py``) and the architecture module
(``archs/<name>.py``: program config, weight tree and counts).
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = CHECKOUT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def config_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", f"{name}.json")


def cell_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "cells", f"{name}.json")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", f"{name}.py")


def reference_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "references", f"{name}.py")


def arch_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "archs", f"{name}.py")


def _load_module(path: str, modname: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The reader module of per-layer metric ``name``: it defines
    ``read(ctx) -> float | None``."""
    mod = _load_module(metric_path(name), f"chipbench_metric_{name}")
    if not callable(getattr(mod, "read", None)):
        raise TypeError(f"{metric_path(name)} defines no read(ctx)")
    return mod


def reference(name: str):
    """The plain reference module a configuration names."""
    return _load_module(reference_path(name), f"chipbench_ref_{name}")


def arch(name: str):
    """The architecture module a configuration names.  It defines
    ``program_config(name, cfg)``, ``weight_shapes(cfg)``,
    ``params(cfg)``, ``prefill_flops(cfg, prompt_len)``,
    ``decode_flops(cfg, contexts)``, ``decode_bytes(cfg, contexts)`` and
    ``page_live_bytes(cfg, prompt_len)``."""
    return _cached_module(arch_path(name), f"chipbench_arch_{name}")


@functools.lru_cache(maxsize=None)
def _cached_module(path: str, modname: str):
    # the counts are asked for once per traced step: load each file once
    return _load_module(path, modname)


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a device not in the table is an error."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return table[device_kind]


def resolve(workload: str, bench: Optional[dict] = None) -> Dict[str, Any]:
    """Everything one cell is made of, as plain data."""
    bench = benchmark() if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = load_json(cell_path(workload))
    for key in ("config", "traffic"):
        if cell[key] != entry[key]:
            raise ValueError(f"cells/{workload}.json says {key}="
                             f"{cell[key]!r}, BENCHMARK.json says "
                             f"{entry[key]!r}")
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return {"name": workload, "entry": entry, "cell": cell,
            "config": load_json(config_path(entry["config"])),
            "traffic": load_json(traffic_path(entry["traffic"])),
            "end_to_end": end_to_end, "per_layer": per_layer}
