"""Drive the program's normal serve path with an open-loop schedule.

The window runs ``ServeEngine.submit``/``step`` and nothing else: each
request is submitted when it is due, ``step()`` runs in a single-threaded
loop, and the loop sleeps to the next due time only while the engine is
idle.  After every ``step()`` the loop stamps ``perf_counter`` on each
request whose ``out_tokens`` grew, so every per-token time comes from
this file's own clock, and every latency is timed from the due time the
schedule holds, not from when the program saw the request.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Callable, List, Optional

import numpy as np

from chipbench import spec, weights
from chipbench.traffic_gen import Arrival


def program_config(name: str, cfg: dict):
    """The program's ``ModelConfig`` for a configuration file, built by
    the architecture module its ``reference`` names."""
    return spec.arch(cfg["reference"]).program_config(name, cfg)


def check_param_layout(pcfg, cfg: dict) -> None:
    """The benchmark's weight tree must be exactly the tree the program
    takes: same structure, shapes and dtypes."""
    import jax
    from repro.models import transformer as T
    want = T.tree_abstract(T.param_defs(pcfg), pcfg)
    have = weights.abstract(cfg)
    sig = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    if jax.tree.structure(want) != jax.tree.structure(have) or \
            sig(want) != sig(have):
        raise ValueError("benchmark weight tree differs from the "
                         "program's parameter tree")


def build_engine(pcfg, params, cfg: dict, cell: dict):
    """The engine as the configuration deploys it (its ``engine``
    options: where KV lives and how pages are installed), at the cell's
    slots and ``max_len``."""
    from repro.serving.engine import ServeEngine
    return ServeEngine(pcfg, params, batch_slots=int(cell["slots"]),
                       max_len=int(cell["max_len"]), **cfg["engine"])


def close_engine(eng) -> None:
    """Join the memory plane's threads and drop the engine's arrays."""
    if eng.pager is not None:
        eng.pager.close()
    eng.caches = None
    eng.params = None


@dataclasses.dataclass
class Rec:
    """One request as the load generator sees it."""
    arr: Arrival
    due_pc: float
    req: object = None           # the program's Request once submitted
    submit_pc: float = 0.0
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def served(self) -> bool:
        return self.done and self.req.failed is None


class Compiles:
    """Counts compilations and persistent-cache loads while armed."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_loads = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if not self.armed:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_loads += 1


def _make_request(rec: Rec):
    from repro.serving.engine import Request
    return Request(rid=rec.arr.rid, prompt=rec.arr.prompt,
                   max_new=rec.arr.out_len)


class Loop:
    """The single-threaded open-loop client of one engine.

    ``run(arrivals, origin_pc, stop_submit_pc, grace_pc)`` submits every
    arrival due before ``stop_submit_pc`` at its due time, steps the
    engine while it has work, and returns once every submitted request
    is done or ``grace_pc`` has passed.  ``on_tick(now)`` (if given) runs
    at the top of each pass; the time it holds the loop (a profiler
    writing its trace) is the harness's, so it moves ``grace_pc`` on and
    never uses up the program's grace.  ``on_step(start, end, grew)``
    (if given) sees each step's start and end time and the ``(rec,
    first_index, count)`` of every request whose tokens grew.
    """

    def __init__(self, eng, annotate: bool = False,
                 on_tick: Optional[Callable[[float], None]] = None,
                 on_step: Optional[Callable] = None):
        self.eng = eng
        self.on_tick = on_tick
        self.on_step = on_step
        self.steps = 0
        self.step_times: List[tuple] = []     # (start, end) of each step
        if annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation
        else:
            self._ann = lambda name: contextlib.nullcontext()

    def run(self, arrivals: List[Arrival], origin_pc: float,
            stop_submit_pc: float, grace_pc: float) -> List[Rec]:
        recs = [Rec(arr=a, due_pc=origin_pc + a.due_s) for a in arrivals
                if origin_pc + a.due_s < stop_submit_pc]
        live: List[Rec] = []
        nxt = 0
        eng = self.eng
        while True:
            now = time.perf_counter()
            if self.on_tick is not None:
                self.on_tick(now)
                grace_pc += time.perf_counter() - now
            while nxt < len(recs) and recs[nxt].due_pc <= now:
                rec = recs[nxt]
                with self._ann("bench.submit"):
                    rec.req = _make_request(rec)
                    eng.submit(rec.req)
                rec.submit_pc = time.perf_counter()
                live.append(rec)
                nxt += 1
            if not live:
                if nxt == len(recs):
                    break
                with self._ann("bench.idle_wait"):
                    time.sleep(max(0.0, recs[nxt].due_pc - now))
                continue
            if now >= grace_pc:
                break
            t_step = time.perf_counter()
            with self._ann("bench.step"):
                eng.step()
            self.steps += 1
            t = time.perf_counter()
            self.step_times.append((t_step, t))
            grew = []
            still = []
            for rec in live:
                n_old = len(rec.stamps)
                n_new = len(rec.req.out_tokens)
                if n_new > n_old:
                    rec.stamps.extend(itertools.repeat(t, n_new - n_old))
                    grew.append((rec, n_old, n_new - n_old))
                if rec.req.t_done_pc > 0.0:
                    rec.done = True
                else:
                    still.append(rec)
            live = still
            if self.on_step is not None:
                self.on_step(t_step, t, grew)
        return recs


# the largest fetch group warmed: one admission round's pages at the cells'
# rates stay well under it
WARM_GROUP_PAGES = 8


def warm_engine(eng, buckets: List[int], vocab: int) -> None:
    """Compile, or load from the persistent cache, every program the
    cell's traffic can run: one prefill per prompt-length bucket (each
    request also runs a decode step, and in a paged cell one page round
    trip), then, in a paged cell, the split of one page out of every
    fetch group of 2 to ``WARM_GROUP_PAGES`` pages (one admission round's
    pages are fetched as one group)."""
    rng = np.random.default_rng(0)
    loop = Loop(eng)
    arrivals = [Arrival(rid=-(i + 1), due_s=0.0,
                        prompt=rng.integers(0, vocab, size=p
                                            ).astype(np.int32),
                        out_len=2)
                for i, p in enumerate(buckets)]
    t = time.perf_counter()
    for a in arrivals:
        loop.run([a], t, t + 1.0, t + 3600.0)
    if eng.pager is not None:
        _warm_group_split(eng, WARM_GROUP_PAGES)


def _warm_group_split(eng, group_pages: int) -> None:
    import jax
    from repro.rmem.store import _device_row
    layout = eng._install_layout()
    words = layout.page_bytes // layout.word_dtype.itemsize
    dev = jax.devices()[0]
    for k in range(2, group_pages + 1):
        buf = jax.device_put(np.zeros((k, words), layout.word_dtype), dev)
        jax.block_until_ready(_device_row(buf, 0))
