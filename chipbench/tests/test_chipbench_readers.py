"""The readers of the program's stamps and spans count every request due
in the window, not only those inside the profiler's span."""
import _paths  # noqa: F401
import types

import pytest

from chipbench import spec


def _rec(rid, due, admit):
    return types.SimpleNamespace(
        arr=types.SimpleNamespace(rid=rid), due_pc=due,
        req=types.SimpleNamespace(t_admit_pc=admit))


def _ctx():
    # window [10, 20); the profiler's span [12, 14) holds one admission
    recs = [_rec(0, 9.0, 9.5), _rec(1, 10.0, 10.1), _rec(2, 12.5, 12.8),
            _rec(3, 15.0, 15.3), _rec(4, 19.0, 19.4), _rec(5, 21.0, 21.9)]
    events = []
    for r, end, first in ((0, 9.6, 9.7), (1, 10.2, 10.3), (2, 12.9, 13.3),
                          (3, 15.4, 15.9), (4, 19.5, 20.5), (5, 22.0, 22.1)):
        events += [{"ph": "B", "tid": 1, "name": "serve.prefill",
                    "ts": (end - 0.05) * 1e6, "args": {"rid": r}},
                   {"ph": "E", "tid": 1, "ts": end * 1e6},
                   {"ph": "i", "name": "serve.first_token",
                    "ts": first * 1e6, "args": {"rid": r}}]
    return types.SimpleNamespace(recs=recs, w0=10.0, w1=20.0, t0=12.0,
                                 t1=14.0, obs_events=events, obs_epoch=0.0,
                                 store0={})


def test_queue_wait_counts_every_request_of_the_window():
    # waits 0.1, 0.3, 0.3, 0.4 s (requests 1-4); median by nearest rank
    got = spec.metric_reader("queue_wait_p50_ms").read(_ctx())
    assert got == pytest.approx(300.0)


def test_page_wait_counts_every_request_of_the_window():
    # prefill end -> first token: 0.1, 0.4, 0.5, 1.0 s (requests 1-4)
    reader = spec.metric_reader("kv_page_wait_p50_ms")
    assert reader.read(_ctx()) == pytest.approx(400.0)
    assert reader.read(types.SimpleNamespace(**{**vars(_ctx()),
                                                "store0": None})) is None


def test_traced_toy_run_reports_the_span_readers():
    """A whole traced run (toy size, CPU; the look for a chip skipped):
    the obs tracer outlives the profiler's span and the readers of the
    program's stamps and spans find every request of the window."""
    import tiny
    from chipbench import run
    names = ["queue_wait_p50_ms", "kv_page_wait_p50_ms",
             "kv_bytes_per_req_mb"]
    args = run.parse(["--workload", "toy", "--seed", str(2**31 + 40),
                      "--seconds", "2", "--trace", "1"])
    result, lines = run.run(args, require_tpu=False,
                            resolved=tiny.resolved(True, per_layer=names))
    assert result["correct"], lines
    assert set(names) <= set(result["metrics"])
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
