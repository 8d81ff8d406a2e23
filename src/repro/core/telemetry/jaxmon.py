"""jax.monitoring bridge: count compilations as telemetry events.

PR 7's static auditor proves *statically* that no backend builder bakes
scalars into its compile key; this is the runtime twin — a recompile storm
(the fixed-shape serving engine retracing mid-benchmark, a tuning sweep
recompiling per point) becomes a visible counter in every trace.

jax 0.4.37 reports compilation through ``jax.monitoring`` duration events:

  * ``/jax/core/compile/backend_compile_duration`` — one per XLA backend
    compile (the expensive step; this is what we count as a compilation),
  * ``/jax/core/compile/jaxpr_trace_duration`` — one per Python trace,
  * ``/jax/compilation_cache/*`` plain events — persistent-cache hits.

``install()`` registers one forwarding listener, once per process
(jax.monitoring has no per-listener unregister, and
``clear_event_listeners`` would nuke listeners we don't own).  The listener
reads the *current* global recorder on every event, so disabling telemetry
makes it a cheap no-op and re-enabling picks the new recorder up without
re-registration.  Counter names are the jax event path with ``/`` -> ``.``
(``jax.core.compile.jaxpr_trace_duration``); the backend compile
additionally lands as a ``jax.compile`` span so summarize reports
compile-time percentiles, and as the :data:`COMPILE_COUNTER` aggregate.
"""

from __future__ import annotations

import threading
from typing import Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"

#: aggregated-counter name for backend compiles (the "recompile storm"
#: runtime metric)
COMPILE_COUNTER = "jax.compile.backend_compile"

_installed = False
_lock = threading.Lock()


def _counter_name(event: str) -> str:
    # "/jax/core/compile/jaxpr_trace_duration" -> "jax.core.compile...."
    return event.strip("/").replace("/", ".")


def install() -> bool:
    """Register the forwarding listeners (idempotent).  Returns True when
    the listeners are active, False when jax is unimportable."""
    global _installed
    with _lock:
        if _installed:
            return True
        try:
            from jax import monitoring
        except Exception:  # pragma: no cover - jax always present here
            return False

        def on_duration(event: str, duration: float, **kw) -> None:
            rec = _current()
            if rec is None or not event.startswith("/jax/"):
                return
            if event == BACKEND_COMPILE:
                rec.counter(COMPILE_COUNTER, proc="jax")
                # a span with an end-anchored window: monitoring reports
                # duration only, so place it ending "now"
                rec._record({
                    "kind": "span", "name": "jax.compile",
                    "ts": max(rec._now() - duration, 0.0),
                    "dur": duration, "sid": next(rec._ids), "parent": None,
                    "proc": "jax",
                    "tid": threading.current_thread().name, "attrs": {}})
            elif event == TRACE:
                rec.counter(_counter_name(event), proc="jax")

        def on_event(event: str, **kw) -> None:
            rec = _current()
            if rec is None or not event.startswith("/jax/"):
                return
            rec.counter(_counter_name(event), proc="jax")

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        _installed = True
        return True


def _current() -> Optional[object]:
    from repro.core import telemetry
    return telemetry._recorder
