"""The engine step's span tree (DESIGN.md §8.1) and its mirror in the
profiler's trace.

A toy paged per-leaf engine and a toy resident engine run with tracing
on: the spans nest as the step's layers do, each admitted request packs
and spills exactly one page of the page's logical bytes inside its
``serve.prefill``, and the export passes ``obs.validate``.  While a
tracer is on every span is also a ``jax.profiler.TraceAnnotation``;
with tracing off none is built.  jax's compiles become ``jit.compile``
spans and the ``jit.compiles`` counter while the obs plane is active.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduce_for_smoke
from repro.models import transformer as T
from repro.obs.validate import validate_trace
from repro.serving import Request, ServeEngine
from repro.serving.engine import page_bytes_for

MAX_LEN = 64


@pytest.fixture(autouse=True)
def _obs_off():
    obs.trace.disable()
    obs.metrics.disable_live()
    obs.default_registry().clear()
    yield
    obs.trace.disable()
    obs.metrics.disable_live()
    obs.default_registry().clear()


@pytest.fixture(scope="module")
def model():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    params = T.tree_init(T.param_defs(cfg), cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(model, paged: bool) -> ServeEngine:
    cfg, params = model
    kw = {"access_path": "xdma", "fused_install": False} if paged else {}
    return ServeEngine(cfg, params, batch_slots=2, max_len=MAX_LEN, **kw)


def _serve(eng: ServeEngine, n: int = 3) -> None:
    rng = np.random.default_rng(0)
    for rid in range(n):
        eng.submit(Request(rid=rid, max_new=3 + rid,
                           prompt=rng.integers(0, 256, 6 + 2 * rid,
                                               dtype=np.int32)))
    assert eng.run_until_drained(max_steps=200) == 0
    if eng.pager is not None:
        eng.pager.close()


def _spans(events):
    """``B``/``E`` pairs as dicts: name, start, end, args (the ``E``'s
    merged in), track and the enclosing span."""
    out, stacks = [], {}
    for ev in events:
        if ev["ph"] == "B":
            st = stacks.setdefault(ev["tid"], [])
            sp = {"name": ev["name"], "t0": ev["ts"],
                  "args": dict(ev.get("args", {})), "tid": ev["tid"],
                  "parent": st[-1] if st else None}
            st.append(sp)
        elif ev["ph"] == "E":
            sp = stacks[ev["tid"]].pop()
            sp["t1"] = ev["ts"]
            sp["args"].update(ev.get("args", {}))
            out.append(sp)
    return out


def _traced_run(model, paged, tmp_path):
    tracer = obs.trace.enable()
    eng = _engine(model, paged)
    _serve(eng)
    path = str(tmp_path / "trace.json")
    tracer.export(path)
    validate_trace(path, require_cats=["serve", "model"]
                   + (["kv"] if paged else []))
    return eng, _spans(tracer.chrome_trace()["traceEvents"])


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "resident"])
def test_step_span_tree(model, paged, tmp_path):
    eng, spans = _traced_run(model, paged, tmp_path)
    steps = _named(spans, "serve.step")
    assert len(steps) == eng._step_no
    assert [s["args"]["step"] for s in steps] == list(range(1, len(steps)
                                                           + 1))
    for name in ("serve.admit", "serve.decode_step", "serve.retire"):
        assert all(s["parent"]["name"] == "serve.step"
                   for s in _named(spans, name)), name
    for name in ("serve.decode.dispatch", "serve.decode.sync"):
        assert all(s["parent"]["name"] == "serve.decode_step"
                   for s in _named(spans, name)), name
    syncs = _named(spans, "serve.decode.sync")
    for st in steps:
        inside = [s for s in syncs if st["t0"] <= s["t0"] <= st["t1"]]
        assert len(inside) <= 1
        assert len(inside) == (1 if st["args"]["active"] else 0)
    decodes = _named(spans, "serve.decode_step")
    assert all(d["args"]["active"] == d["parent"]["args"]["active"]
               for d in decodes)
    assert sum(s["args"]["finished"]
               for s in _named(spans, "serve.retire")) == 3
    prefills = _named(spans, "serve.prefill")
    assert sorted(p["args"]["rid"] for p in prefills) == [0, 1, 2]
    assert all(p["parent"]["name"] == "serve.admit" for p in prefills)
    model_prefills = _named(spans, "model.prefill")
    assert [m["parent"] for m in model_prefills] == prefills
    if not paged:
        assert not [s for s in spans if s["name"].startswith("kv.")]
        assert not _named(spans, "serve.install_wait")
        return
    page = page_bytes_for(eng.cfg, MAX_LEN)
    for p in prefills:
        packs = [s for s in _named(spans, "kv.pack") if s["parent"] is p]
        spills = [s for s in _named(spans, "kv.spill") if s["parent"] is p]
        assert len(packs) == len(spills) == 1
        pack, spill = packs[0], spills[0]
        rid = p["args"]["rid"]
        assert pack["args"] == spill["args"] == {"rid": rid, "bytes": page}
        model_p = next(m for m in model_prefills if m["parent"] is p)
        # serve.prefill: from before model.prefill to after kv.spill
        assert p["t0"] <= model_p["t0"] <= model_p["t1"] <= pack["t0"]
        assert pack["t1"] <= spill["t0"] <= spill["t1"] <= p["t1"]
    assert len(_named(spans, "kv.pack")) == 3
    waits = _named(spans, "serve.install_wait")
    assert waits and all(w["args"]["pending"] >= 1 for w in waits)
    assert all(w["parent"]["name"] == "serve.step" for w in waits)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what it
    is built with, entered and exited."""
    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _Recorder.log.append(("enter", self.name, dict(self.kwargs)))

    def __exit__(self, *exc):
        _Recorder.log.append(("exit", self.name, dict(self.kwargs)))

    def set_metadata(self, **kwargs):
        self.kwargs.update(kwargs)


def test_spans_mirror_into_profiler_annotations(monkeypatch):
    _Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    obs.trace.enable()
    with obs.span("serve.install", rid=3, rids=[1, 2], share=0.5,
                  word=np.int64(7)) as sp:
        with obs.span("tier.inner"):
            pass
        sp.set(slots=2)
    obs.instant("serve.first_token", rid=3)         # ring only
    obs.complete("cplane.op", 0.0, 1e-3)            # ring only
    want = {"rid": 3, "rids": "[1, 2]", "share": 0.5, "word": "7"}
    assert _Recorder.log == [
        ("enter", "serve.install", want),
        ("enter", "tier.inner", {}), ("exit", "tier.inner", {}),
        ("exit", "serve.install", {**want, "slots": 2})]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "resident"])
def test_tracing_off_builds_no_annotation(model, paged, monkeypatch):
    class _Refuse:
        def __init__(self, *a, **k):
            raise AssertionError("TraceAnnotation built with tracing off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refuse)
    assert obs.span("serve.step", step=1) is obs.trace._NULL_SPAN
    eng = _engine(model, paged)
    _serve(eng)
    assert len(eng.done) == 3 and all(r.failed is None for r in eng.done)


def test_compiles_become_spans_and_a_counter():
    fresh = lambda: jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))  # noqa: E731
    fresh().block_until_ready()             # plane off: nothing counted
    assert obs.default_registry().get("jit.compiles") is None
    tracer = obs.trace.enable()
    assert obs.default_registry().get("jit.compiles").value == 0
    with obs.span("serve.step"):
        fresh().block_until_ready()
    n = obs.default_registry().get("jit.compiles").value
    assert n >= 1
    events = tracer.chrome_trace()["traceEvents"]
    compiles = [e for e in events if e["name"] == "jit.compile"]
    step = next(e for e in events if e["name"] == "serve.step")
    assert len(compiles) == n
    assert all(e["ph"] == "X" and e["tid"] == step["tid"] and e["dur"] > 0
               for e in compiles)
    obs.trace.disable()
    obs.metrics.enable_live()               # the counter alone
    fresh().block_until_ready()
    assert obs.default_registry().get("jit.compiles").value > n


def test_serve_cli_metrics_carry_the_compile_count():
    from repro.launch.serve import main
    res = main(["--smoke", "--requests", "2", "--max-new", "3",
                "--slots", "2", "--prompt-len", "6", "--metrics"])
    assert res["metrics"]["jit.compiles"] >= 0
