"""Helpers the metric readers share: percentiles, the traced steps, and
the obs spans of the traced window on the trace's clock.

A reader (``metrics/<name>.py``) gets one context ``ctx`` with:

* ``cfg``, ``cell``, ``mix``, ``peaks``: the cell's data;
* ``recs``: every request of the run (``driver.Rec``), ``w0``/``w1``
  the window's bounds and ``setup_s`` the set-up seconds;
* in a traced run also ``t0``/``t1`` (the profiler's span, perf_counter
  seconds), ``steps`` (``(start, end, prefill_lens, decode_contexts)``
  of every engine step run while tracing), ``trace`` (see
  ``trace_reduce.load``), ``offset_ns`` (perf_counter -> trace clock),
  ``obs_events``/``obs_epoch`` (the program's own obs spans, from the
  ramp's start to the drain's end) and
  ``store0``/``store1`` (the memory plane's stats at both ends, or
  None for a cell without one).
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (no interpolation); None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    k = max(0, math.ceil(q / 100.0 * len(vals)) - 1)
    return float(vals[k])


def window_recs(ctx) -> list:
    """The requests due in the window."""
    return [r for r in ctx.recs if ctx.w0 <= r.due_pc < ctx.w1]


def ttft_values(ctx) -> List[float]:
    """First-token stamp minus due time of every request due in the
    window; one never served waits until the drain's grace ended."""
    return [(r.stamps[0] if r.stamps and r.served else ctx.grace_end)
            - r.due_pc for r in window_recs(ctx)]


def token_gaps(ctx) -> List[float]:
    """Every gap between consecutive token stamps of every request, for
    tokens stamped inside the window."""
    gaps = []
    for r in ctx.recs:
        s = r.stamps
        gaps.extend(s[i] - s[i - 1] for i in range(1, len(s))
                    if ctx.w0 <= s[i] < ctx.w1)
    return gaps


def steps_in_trace(ctx) -> List[tuple]:
    """Engine steps that ran wholly while the profiler was on."""
    return [s for s in ctx.steps if s[0] >= ctx.t0 and s[1] <= ctx.t1]


def steps_window_ns(ctx) -> Optional[Tuple[float, float]]:
    """The trace-clock interval of the wholly traced steps."""
    steps = steps_in_trace(ctx)
    if not steps or ctx.offset_ns is None:
        return None
    return (steps[0][0] * 1e9 + ctx.offset_ns,
            steps[-1][1] * 1e9 + ctx.offset_ns)


def obs_spans(ctx, names: Optional[set] = None) -> List[tuple]:
    """The program's obs spans as ``(name, start_pc, end_pc, args)``:
    ``B``/``E`` pairs matched per track, and ``X`` complete events."""
    out, stacks = [], {}
    for ev in ctx.obs_events:
        ph = ev.get("ph")
        ts = ctx.obs_epoch + ev.get("ts", 0.0) / 1e6
        if ph == "B":
            stacks.setdefault(ev["tid"], []).append((ev["name"], ts,
                                                     ev.get("args", {})))
        elif ph == "E":
            st = stacks.get(ev["tid"])
            if st:
                name, t0, args = st.pop()
                out.append((name, t0, ts, args))
        elif ph == "X":
            out.append((ev["name"], ts, ts + ev.get("dur", 0.0) / 1e6,
                        ev.get("args", {})))
    if names is not None:
        out = [s for s in out if s[0] in names]
    return sorted(out, key=lambda s: s[1])


def obs_instants(ctx, name: str) -> List[tuple]:
    """``(pc, args)`` of every instant ``name`` in the obs events."""
    return [(ctx.obs_epoch + ev["ts"] / 1e6, ev.get("args", {}))
            for ev in ctx.obs_events
            if ev.get("ph") == "i" and ev.get("name") == name]


def share(numerator: float, denominator: float) -> Optional[float]:
    """A share in percent, or None when there is nothing to divide."""
    if not denominator or denominator <= 0 or numerator <= 0:
        return None
    return 100.0 * numerator / denominator
