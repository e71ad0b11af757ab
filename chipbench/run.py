"""Run one benchmark cell once and print its result as one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the window's
opening): the compile cache, weights from the seed on the device, one
prefill per prompt-length bucket of the mix (each with a decode step
and, in a paged cell, a page round trip), the split of a page out of
every fetch group up to ``driver.WARM_GROUP_PAGES`` pages, and a ramp
of the cell's own traffic so slots are occupied when the window opens.
The window then drives ``ServeEngine.submit``/``step`` open loop for
``--seconds``; arrivals stop at its close and the window's requests
drain under a bounded grace.  Then the peak device
memory is read, the engine is freed, and the served tokens of a seeded
sample are checked against the float32 reference (``check.py``).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of a few seconds of the
window and from the program's stamps and obs spans over all of it.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, driver, readlib, spec, trace_reduce  # noqa: E402
from chipbench import traffic_gen, weights  # noqa: E402

OUT_DIR = os.path.join(CHECKOUT, ".bench_out")
DRAIN_GRACE_S = 120.0   # after the window, for its requests to finish
TRACE_LEAD_S = 3.0      # from the window's opening to the profiler's span
TRACE_S = 8.0           # the profiler's span (a traced run's device window)


class BenchError(RuntimeError):
    """The run cannot measure (no chip, unknown device, bad data)."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Tracing:
    """What a traced run records.  The obs tracer is on from before the
    schedule's first arrival until the drain ends, so the readers of the
    program's spans see every request of the window.  The profiler is on
    from the same start (starting it then stalls no request) until
    ``t_end``, the window's close: stopping it writes the trace and
    blocks the loop for seconds, so it stops where that stall can delay
    only the few requests still waiting as the window closes, not the
    window's admissions.  The traced window is ``[t_start, t_stop)``,
    and every engine step run while the profiler is on is recorded."""

    def __init__(self, eng, trace_dir: str):
        self.t_start = self.t_stop = self.t_end = float("inf")
        self.eng = eng
        self.dir = trace_dir
        self.on = False
        self.t0 = self.t1 = None
        self.steps = []
        self.store0 = self.store1 = None
        self.obs_events, self.obs_epoch = [], 0.0

    def begin(self) -> None:
        import jax
        from repro import obs
        shutil.rmtree(self.dir, ignore_errors=True)
        tracer = obs.trace.enable(limit=1 << 20)
        self.obs_epoch = tracer.epoch
        jax.profiler.start_trace(self.dir)
        self.on = True

    def tick(self, now: float) -> None:
        if self.on and self.t0 is None and now >= self.t_start:
            self.t0 = now
            self.store0 = self._store()
        elif self.t0 is not None and self.t1 is None and now >= self.t_stop:
            self._close_span()
        if self.on and now >= self.t_end:
            self.stop()

    def _close_span(self) -> None:
        self.t1 = time.perf_counter()
        self.store1 = self._store()

    def stop(self) -> None:
        """End the traced window if it is still open, and the profiler."""
        import jax
        if self.t1 is None:
            self._close_span()
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False
        log(f"profiler stop held the loop {time.perf_counter() - t} s")

    def finish(self) -> None:
        """After the drain: end the profiler's span if it is still on,
        and collect the obs events."""
        from repro import obs
        if self.on:
            self.stop()
        self.obs_events = obs.get_tracer().chrome_trace()["traceEvents"]
        obs.trace.disable()

    def _store(self):
        return None if self.eng.pager is None else self.eng.pager.stats()

    def step(self, t_start: float, t_end: float, grew) -> None:
        """Token 0 of a request comes from its prefill (recorded as the
        prompt length); decode token j attends over prompt + j keys."""
        if not self.on:
            return
        prefill, decode = [], []
        for rec, first, count in grew:
            plen = len(rec.arr.prompt)
            for j in range(first, first + count):
                (prefill if j == 0 else decode).append(plen + j)
        self.steps.append((t_start, t_end, prefill, decode))

    def xplane(self) -> str:
        for root, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        raise BenchError(f"profiler wrote no .xplane.pb under {self.dir}")


def end_to_end_ctx(r, recs, w0, w1, setup_s, grace_end):
    return types.SimpleNamespace(
        cfg=r["config"], cell=r["cell"], mix=r["traffic"], peaks=r["peaks"],
        recs=recs, w0=w0, w1=w1, setup_s=setup_s, grace_end=grace_end)


def read_metrics(metric_entries, ctx) -> dict:
    out = {}
    for m in metric_entries:
        val = spec.metric_reader(m["name"]).read(ctx)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def trace_ctx(ctx, tr: Tracing):
    """Reduce the trace; extend ``ctx`` for the per-layer readers;
    return the ``device`` timing and the breakdown."""
    trace = trace_reduce.load(tr.xplane())
    host_steps = [s for name, s, _ in trace["host"] if name == "bench.step"]
    offset = trace_reduce.clock_offset_ns(
        host_steps, [s[0] for s in tr.steps])
    ctx.t0, ctx.t1 = tr.t0, tr.t1
    ctx.steps = tr.steps
    ctx.trace = trace
    ctx.offset_ns = offset
    ctx.obs_events, ctx.obs_epoch = tr.obs_events, tr.obs_epoch
    ctx.store0, ctx.store1 = tr.store0, tr.store1
    if offset is not None:
        lo, hi = tr.t0 * 1e9 + offset, tr.t1 * 1e9 + offset
    else:
        lo, hi = trace_reduce.extent(trace) or (0.0, 0.0)
    ctx.trace_window_ns = (lo, hi)
    busy = trace_reduce.busy_ns(trace, lo, hi) / 1e9
    window = (hi - lo) / 1e9
    labelled = [(name, s, s + d) for name, s, d in trace["host"]]
    if offset is not None:
        labelled += [(name, s * 1e9 + offset, e * 1e9 + offset)
                     for name, s, e, _ in readlib.obs_spans(ctx)]
    gaps = trace_reduce.idle_gaps(trace, lo, hi)
    breakdown = {"device_ops": trace_reduce.top_ops(trace, lo, hi),
                 "idle_gaps": trace_reduce.attribute_gaps(gaps, labelled)}
    return {"busy_s": busy, "window_s": window}, breakdown


class Prepared:
    """What set-up builds once per process: the device, the weights of
    one seed, the engine warmed on every shape the cell's traffic uses,
    and the reference module."""

    def __init__(self, r: dict, seed: int, require_tpu: bool = True,
                 hooks=None):
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        import jax
        self.devs = jax.devices()
        self.dev = self.devs[0]
        chips = int(r["entry"]["chips"])
        if require_tpu and self.dev.platform != "tpu":
            raise BenchError(f"JAX found no TPU (device 0 is "
                             f"{self.dev.platform}); this benchmark "
                             f"measures only on the chip")
        if len(self.devs) < chips:
            raise BenchError(f"cell needs {chips} chips, JAX found "
                             f"{len(self.devs)}")
        if "peaks" not in r:
            r["peaks"] = spec.peaks(self.dev.device_kind)
        self.r = r
        cfg, cell = r["config"], r["cell"]
        self.ref = spec.reference(cfg["reference"])
        log(f"workload={r['name']} seed={seed} device="
            f"{self.dev.device_kind} count={len(self.devs)} "
            f"compile_cache={cache_dir}")
        pcfg = driver.program_config(r["entry"]["config"], cfg)
        driver.check_param_layout(pcfg, cfg)
        self.params = weights.init(cfg, seed)
        jax.block_until_ready(self.params)
        self.eng = driver.build_engine(pcfg, self.params, cfg, cell)
        if hooks and "engine" in hooks:
            hooks["engine"](self.eng)
        driver.warm_engine(self.eng, traffic_gen.prompt_buckets(r["traffic"]),
                           cfg["vocab_size"])
        self.compiles = driver.Compiles()

    def reseed(self, seed: int) -> None:
        """New weights for another seed in the same engine (the programs
        take the weights as an argument, so nothing recompiles)."""
        import jax
        self.eng.params = self.params = None
        gc.collect()
        self.params = weights.init(self.r["config"], seed)
        jax.block_until_ready(self.params)
        self.eng.params = self.params

    def window(self, seed: int, seconds: float, rate: float,
               trace: bool = False):
        """Ramp, window and drain of one schedule; returns the context
        the end-to-end readers take and the tracer (or None)."""
        r, eng, compiles = self.r, self.eng, self.compiles
        cell = r["cell"]
        ramp = float(cell["ramp_s"])
        arrivals = traffic_gen.schedule(r["traffic"], rate, ramp + seconds,
                                        r["config"]["vocab_size"], seed)
        tr = None
        if trace:
            tr = Tracing(eng, os.path.join(OUT_DIR, "trace"))
            tr.begin()
        origin = time.perf_counter()
        w0, w1 = origin + ramp, origin + ramp + seconds
        grace_end = w1 + DRAIN_GRACE_S
        if tr is not None:
            lead = min(TRACE_LEAD_S, seconds / 4)
            span = min(TRACE_S, seconds - lead)
            tr.t_start, tr.t_stop = w0 + lead, w0 + lead + span
            tr.t_end = w1
        compiles.compiles = compiles.cache_loads = 0

        def tick(now):
            compiles.armed = w0 <= now < w1
            if tr is not None:
                tr.tick(now)

        loop = driver.Loop(eng, annotate=trace, on_tick=tick,
                           on_step=tr.step if tr is not None else None)
        recs = loop.run(arrivals, origin, w1, grace_end)
        compiles.armed = False
        if tr is not None:
            tr.finish()
        ctx = end_to_end_ctx(r, recs, w0, w1, w0 - T_START, grace_end)
        ctx.window = [x for x in recs if w0 <= x.due_pc < w1]
        ctx.unserved = sum(1 for x in ctx.window if not x.served)
        ctx.steps_run = loop.steps
        ctx.step_times = loop.step_times
        lateness = sorted(x.submit_pc - x.due_pc for x in recs
                          if x.submit_pc)
        log(f"attempted={len(ctx.window)} unserved={ctx.unserved} "
            f"steps={loop.steps} compiles_in_window={compiles.compiles} "
            f"cache_loads_in_window={compiles.cache_loads}")
        if lateness:
            log(f"generator lateness (due -> submit) ms: "
                f"p50={1e3 * readlib.percentile(lateness, 50)} "
                f"p95={1e3 * readlib.percentile(lateness, 95)} "
                f"max={1e3 * lateness[-1]}")
        return ctx, tr

    def free_engine(self) -> None:
        driver.close_engine(self.eng)
        self.eng = None
        gc.collect()


def run(args, require_tpu: bool = True, resolved=None, hooks=None):
    """One run; returns the result dict (the last line) and the check
    lines.  ``hooks`` (tests only) may replace steps of the run."""
    hooks = hooks or {}
    r = resolved if resolved is not None else spec.resolve(args.workload)
    prep = Prepared(r, args.seed, require_tpu, hooks)
    cell = r["cell"]
    ctx, tr = prep.window(args.seed, args.seconds, float(cell["rate_rps"]),
                          bool(args.trace))
    mem = prep.dev.memory_stats() or {}
    device = {"platform": prep.dev.platform, "kind": prep.dev.device_kind,
              "count": len(prep.devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": False, "attempted": len(ctx.window),
              "failed": ctx.unserved}
    if tr is None:
        result["metrics"] = read_metrics(r["end_to_end"], ctx)
        result["device"] = device
    else:
        timing, breakdown = trace_ctx(ctx, tr)
        result["metrics"] = read_metrics(r["per_layer"], ctx)
        result["device"] = {**device, **timing}
        result["breakdown"] = breakdown

    prep.free_engine()
    numbers = check.readings(prep.ref, prep.params, r["config"], cell,
                             r["traffic"], ctx.window, args.seed)
    if "numbers" in hooks:
        numbers = hooks["numbers"](numbers)
    numbers["unserved"] = ctx.unserved
    limits = {"max_logit_gap": float(cell["check"]["max_logit_gap"]),
              "unserved": 0}
    result["correct"] = all(numbers[k] <= lim for k, lim in limits.items()) \
        and numbers["replayed_requests"] > 0
    log(f"replayed {numbers['replayed_requests']} requests, "
        f"{numbers['replayed_tokens']} served tokens")
    result["check"] = check.verdict(numbers, limits)
    lines = [f"check {k}={v['value']} limit={v['limit']}"
             for k, v in result["check"].items()]
    return result, lines


def main(argv=None, require_tpu: bool = True, resolved=None,
         hooks=None) -> int:
    args = parse(argv)
    try:
        result, lines = run(args, require_tpu, resolved, hooks)
    except (BenchError, ImportError, KeyError, FileNotFoundError) as e:
        log(f"FAIL: {type(e).__name__}: {e}")
        return 2
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
