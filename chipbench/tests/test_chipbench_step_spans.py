"""The readers of the program's step and page spans (``kv_pack_p50_ms``,
``kv_spill_p50_ms``, ``step_host_p50_ms``), and the program's spans on
the profiler's clock."""
import _paths  # noqa: F401
import os
import time
import types

import pytest

from chipbench import spec

READERS = ["kv_pack_p50_ms", "kv_spill_p50_ms", "step_host_p50_ms"]


def _span(name, t0, t1, tid=1, **args):
    return [{"ph": "B", "tid": tid, "name": name, "ts": t0 * 1e6,
             "args": args},
            {"ph": "E", "tid": tid, "name": name, "ts": t1 * 1e6}]


def _ctx(**over):
    # window [10, 20): requests 1-4 are due in it, 0 and 5 are not
    recs = [types.SimpleNamespace(arr=types.SimpleNamespace(rid=r),
                                  due_pc=due)
            for r, due in enumerate((9.0, 10.0, 12.5, 15.0, 19.0, 21.0))]
    events = []
    # pack 10, 20, 30, 40, 50, 60 ms; spill twice the pack
    for r, t in enumerate((9.5, 10.5, 13.0, 15.5, 19.5, 21.5)):
        pack = 0.01 * (r + 1)
        events += _span("kv.pack", t, t + pack, rid=r, bytes=4096)
        events += _span("kv.spill", t + pack, t + 3 * pack, rid=r,
                        bytes=4096)
    # steps: (start, length, sync) — the one at 9.9 starts before the
    # window, the one at 20.0 at its close; a step without a decode has
    # no sync
    for t, length, sync in ((9.9, 0.5, 0.4), (11.0, 0.10, 0.07),
                            (12.0, 0.09, 0.08), (13.0, 0.02, None),
                            (14.0, 0.30, 0.25), (20.0, 0.5, 0.1)):
        events += _span("serve.step", t, t + length)[:1]
        if sync is not None:
            events += _span("serve.decode.sync", t + length - sync - 0.001,
                            t + length - 0.001)
        events += _span("serve.step", t, t + length)[1:]
    ctx = dict(recs=recs, w0=10.0, w1=20.0, obs_events=events,
               obs_epoch=0.0, store0={})
    ctx.update(over)
    return types.SimpleNamespace(**ctx)


def test_kv_pack_and_spill_read_every_request_of_the_window():
    # requests 1-4: pack 20, 30, 40, 50 ms -> nearest-rank median 30;
    # spill 40, 60, 80, 100 -> 60
    pack = spec.metric_reader("kv_pack_p50_ms")
    spill = spec.metric_reader("kv_spill_p50_ms")
    assert pack.read(_ctx()) == pytest.approx(30.0)
    assert spill.read(_ctx()) == pytest.approx(60.0)
    # no pager, or a program without the spans: nothing to read
    for reader in (pack, spill):
        assert reader.read(_ctx(store0=None)) is None
        assert reader.read(_ctx(obs_events=[])) is None


def test_step_host_is_step_time_less_its_sync():
    # steps starting in the window: host 30, 10, 20, 50 ms -> median 20
    got = spec.metric_reader("step_host_p50_ms").read(_ctx())
    assert got == pytest.approx(20.0)
    assert spec.metric_reader("step_host_p50_ms").read(
        _ctx(obs_events=[])) is None


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "resident"])
def test_traced_toy_run_reports_the_step_readers(paged, tmp_path,
                                                monkeypatch):
    """A whole traced run (toy size, CPU; the look for a chip skipped):
    the paged cell reads all three, the resident one no page spans.
    The profiler writes under ``tmp_path``, apart from other files'
    traced runs."""
    import tiny
    from chipbench import run
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    args = run.parse(["--workload", "toy", "--seed", str(2**31 + 41),
                      "--seconds", "2", "--trace", "1"])
    result, lines = run.run(args, require_tpu=False,
                            resolved=tiny.resolved(paged, per_layer=READERS))
    assert result["correct"], lines
    want = set(READERS) if paged else {"step_host_p50_ms"}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_spans_share_the_profiler_clock(tmp_path):
    """A profiler run on the CPU around toy engine steps with the obs
    tracer on: ``serve.step``, ``kv.pack`` and ``serve.decode.sync``
    are on the host plane by name, and each obs span's start, mapped
    onto the trace's clock through the ``bench.step`` offset, lies
    within 1 ms of its annotation twin."""
    import jax
    import numpy as np
    import tiny
    from jax.profiler import ProfileData

    from chipbench import driver, trace_reduce, weights
    from chipbench.readlib import obs_spans
    from repro import obs
    from repro.serving.engine import Request

    cfg = tiny.CONFIG
    pcfg = driver.program_config("toy", cfg)
    params = weights.init(cfg, 7)
    eng = driver.build_engine(pcfg, params, {"engine": {
        "access_path": "xdma", "fused_install": False}},
        {"slots": 2, "max_len": 128})
    rng = np.random.default_rng(0)
    for rid in range(3):            # compile outside the profiled steps
        eng.submit(Request(rid=-1 - rid, max_new=2,
                           prompt=rng.integers(0, 512, 16 + 8 * rid,
                                               dtype=np.int32)))
    eng.run_until_drained(max_steps=100)
    names = ("serve.step", "kv.pack", "serve.decode.sync")
    tracer = obs.trace.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        step_pcs = []
        for rid in range(3):
            eng.submit(Request(rid=rid, max_new=3,
                               prompt=rng.integers(0, 512, 16 + 8 * rid,
                                                   dtype=np.int32)))
        while not eng.idle():
            step_pcs.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
        jax.profiler.stop_trace()
    finally:
        events = tracer.chrome_trace()["traceEvents"]
        obs.trace.disable()
        driver.close_engine(eng)
    xplane = next(os.path.join(root, f)
                  for root, _, files in os.walk(tmp_path)
                  for f in files if f.endswith(".xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(e.start_ns)
    offset = trace_reduce.clock_offset_ns(host["bench.step"], step_pcs)
    ctx = types.SimpleNamespace(obs_events=events,
                                obs_epoch=tracer.epoch)
    for name in names:
        twins = sorted(host.get(name, []))
        mine = [s[1] * 1e9 + offset for s in obs_spans(ctx, {name})]
        assert twins and len(twins) == len(mine), name
        gaps = [abs(a - b) for a, b in zip(sorted(mine), twins)]
        assert max(gaps) < 1e6, (name, max(gaps))
