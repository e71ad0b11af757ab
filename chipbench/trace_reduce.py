"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Two steps, kept apart so the second can be checked on a small recorded
trace without a chip:

1. ``load(path)`` reads the trace with ``jax.profiler.ProfileData`` into
   plain lists: every device op (name, start, duration, the program it
   ran in) and every program execution, from the TPU planes' ``XLA
   Ops`` and ``XLA Modules`` lines, and the harness's own host
   annotations (``bench.*``) from the host plane.  Times are in
   nanoseconds on the trace's one clock.
2. The functions below reduce those lists: the union of device-busy
   intervals, the idle share of a window, device time per program
   (found by its jit name),
   and the breakdown of device time and of idle gaps.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

_ID = re.compile(r"\(\d+\)$")


def program_name(module: str) -> str:
    """``jit_decode(123)`` -> ``jit_decode``."""
    return _ID.sub("", module or "")


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: a TPU
    trace names each op by its whole HLO instruction."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _attribute(ops: List[list], modules: List[list]) -> None:
    """Fill in each op's program from the program execution that holds
    its start (TPU ops carry no module name of their own)."""
    by_dev = collections.defaultdict(list)
    for dev, name, s, d in modules:
        by_dev[dev].append((s, s + d, name))
    for runs in by_dev.values():
        runs.sort()
    starts = {dev: [r[0] for r in runs] for dev, runs in by_dev.items()}
    for op in ops:
        if op[4]:
            continue
        runs = by_dev.get(op[0])
        if not runs:
            continue
        i = bisect.bisect_right(starts[op[0]], op[2]) - 1
        if i >= 0 and op[2] < runs[i][1]:
            op[4] = runs[i][2]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):
                                                    ].isdigit()


def load(path: str) -> dict:
    """The trace as plain lists (see the module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[list] = []
    modules: List[list] = []
    host: List[list] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        modules.append([dev, program_name(e.name),
                                        e.start_ns, e.duration_ns])
                elif line.name == "XLA Ops":
                    for e in line.events:
                        stats = dict(e.stats)
                        ops.append([dev, op_name(e.name), e.start_ns,
                                    e.duration_ns, program_name(str(
                                        stats.get("hlo_module", "")))])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, e.start_ns, e.duration_ns])
    _attribute(ops, modules)
    return {"ops": ops, "modules": modules, "host": host}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_intervals(trace: dict, device: int = 0) -> List[Interval]:
    return union((s, s + d) for dev, _, s, d, _ in trace["ops"]
                 if dev == device)


def devices(trace: dict) -> List[int]:
    return sorted({op[0] for op in trace["ops"]})


def busy_ns(trace: dict, lo: float, hi: float) -> float:
    """Device-busy nanoseconds in ``[lo, hi)``, averaged over devices."""
    devs = devices(trace)
    if not devs:
        return 0.0
    total = sum(sum(e - s for s, e in clip(busy_intervals(trace, d), lo, hi))
                for d in devs)
    return total / len(devs)


def extent(trace: dict) -> Optional[Interval]:
    """First start and last end of any device op."""
    if not trace["ops"]:
        return None
    return (min(op[2] for op in trace["ops"]),
            max(op[2] + op[3] for op in trace["ops"]))


def program_runs(trace: dict, name: str, lo: float = float("-inf"),
                 hi: float = float("inf")) -> List[Interval]:
    """Executions of program ``name`` (its jit name, e.g. ``jit_decode``)
    that start in ``[lo, hi)``."""
    return [(s, s + d) for _, mod, s, d in trace["modules"]
            if mod == name and lo <= s < hi]


def seconds(runs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in runs) / 1e9


def top_ops(trace: dict, lo: float, hi: float, n: int = 10
            ) -> List[list]:
    """The ``n`` device ops (by program and op name) that took most
    device time in ``[lo, hi)``, in seconds."""
    acc: Dict[str, float] = collections.defaultdict(float)
    for _, name, s, d, mod in trace["ops"]:
        if lo <= s < hi:
            acc[f"{mod}/{name}" if mod else name] += d / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, lo: float, hi: float, device: int = 0
              ) -> List[Interval]:
    """Device-idle intervals of ``device`` inside ``[lo, hi)``."""
    gaps, t = [], lo
    for s, e in clip(busy_intervals(trace, device), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute_gaps(gaps: Sequence[Interval], labelled: Sequence[tuple],
                   n: int = 10) -> List[list]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost (shortest) ``(label, start_ns, end_ns)`` span that covers
    at least half of it, else to the span covering most of it, else to
    ``"host.other"``.  The ``n`` labels with the most idle time."""
    acc: Dict[str, float] = collections.defaultdict(float)
    spans = sorted(labelled, key=lambda x: x[1])
    starts = [x[1] for x in spans]
    for gs, ge in gaps:
        best, best_cov, inner, inner_len = "host.other", 0.0, None, 0.0
        i = bisect.bisect_right(starts, ge)
        # spans starting before the gap ends, with a bounded look back:
        # spans are short against a trace
        for label, s, e in spans[max(0, i - 256):i]:
            cov = min(e, ge) - max(s, gs)
            if cov <= 0:
                continue
            if cov > best_cov:
                best, best_cov = label, cov
            if 2 * cov >= ge - gs and (inner is None or e - s < inner_len):
                inner, inner_len = label, e - s
        acc[inner or best] += (ge - gs) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def clock_offset_ns(host_starts_ns: Sequence[float],
                    pc_starts: Sequence[float]) -> Optional[float]:
    """Offset mapping ``perf_counter`` seconds onto the trace clock:
    ``trace_ns = pc * 1e9 + offset``.  Pairs the k-th recorded
    ``bench.step`` start with the k-th one in the trace and takes the
    median difference."""
    n = min(len(host_starts_ns), len(pc_starts))
    if n == 0:
        return None
    diffs = sorted(h - p * 1e9 for h, p in
                   zip(sorted(host_starts_ns)[:n], sorted(pc_starts)[:n]))
    return diffs[n // 2]
