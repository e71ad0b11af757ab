"""jax compiles as obs events: one ``jax.monitoring`` listener.

While the obs plane is active (``obs.active()``), every backend compile
jax reports becomes

* a retroactive ``jit.compile`` span on the calling thread's track (jax
  reports the compile's duration right after it, on the thread that
  compiled), so an idle gap of the device can be put down to it; and
* one increment of the counter ``jit.compiles`` in the default registry,
  which the serve CLI's ``--metrics`` snapshot carries.

``install()`` registers the listener once per process; ``trace.enable``
and ``metrics.enable_live`` call it, so importing ``repro.obs`` registers
nothing and needs no jax.
"""
from __future__ import annotations

import threading
import time

from repro.obs import metrics, trace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COUNTER = "jit.compiles"

_lock = threading.Lock()
_installed = False


def _on_duration(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    tracer = trace._TRACER
    if tracer is None and not metrics._LIVE:
        return
    if tracer is not None:
        tracer.complete("jit.compile", time.perf_counter() - secs, secs)
    metrics.default_registry().counter(COUNTER).inc()


def install() -> None:
    """Register the compile listener (once per process) and make the
    counter visible from the first snapshot on, at 0 if nothing has
    compiled yet."""
    global _installed
    metrics.default_registry().counter(COUNTER)
    with _lock:
        if _installed:
            return
        try:
            import jax.monitoring
        except ImportError:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
