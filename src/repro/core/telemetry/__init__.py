"""Registry-wide runtime telemetry (schema ``repro.telemetry/v1``).

One shared substrate for every measured number in the repo: structured
spans (monotonic start/duration, parent nesting) and counters, with two
sinks for spans:

  * the in-memory ring (``REPRO_TELEMETRY``): a thread-safe bounded buffer
    exported as a JSONL event log or a flat metrics snapshot for
    ``BENCH_*.json`` artifacts, and summarized by a CLI::

        python -m repro.core.telemetry summarize <trace>  # p50/p95/p99

  * the JAX profiler's trace: while a profiler session collects
    (``jax.profiler.start_trace``), every ``span`` also opens a
    ``jax.profiler.TraceAnnotation`` of the same name, with its attributes
    as event stats.  The span then lands on the ``/host:CPU`` plane of the
    ``.xplane.pb``, on the device planes' clock, whatever
    ``REPRO_TELEMETRY`` says.  A ``gc.callbacks`` hook likewise brackets
    each garbage collection in a ``python.gc`` span (stat ``generation``).

The ring is controlled from the environment::

    REPRO_TELEMETRY=off          # default: module-level no-op fast path
    REPRO_TELEMETRY=on           # record into the in-memory ring
    REPRO_TELEMETRY=jsonl:PATH   # record + flush the JSONL log at exit
    REPRO_TELEMETRY_CAP=65536    # ring capacity (events)

Instrumentation sites call the module-level helpers::

    from repro.core import telemetry as tel
    with tel.span("serving.decode_step", proc="engine", active=n):
        ...                       # around the jit call, never inside it
    tel.counter("tuning.cache.hit")

With neither sink active ``span`` returns a shared no-op context manager
and ``instant``/``counter`` return immediately: an instrumented hot path
pays a module-attribute load, one ``TraceAnnotation.is_enabled()`` call
and one ``is None`` check.  Events must fire at the Python/driver level
only (trace-time-safe: a jitted consumer emits execution events once per
call, not once per trace), and neither sink may change compiled numerics.
JAX is never imported here: the profiler sink is looked up only once
``jax.profiler`` has been imported, since no session can collect before.

Enabling the ring also installs the ``jax.monitoring`` bridge
(:mod:`repro.core.telemetry.jaxmon`): XLA backend compiles become the
``jax.compile.backend_compile`` counter plus ``jax.compile`` spans, so
recompile storms — the runtime twin of the static auditor's ``recompile``
pass — are visible in every trace.
"""

from __future__ import annotations

import atexit
import gc
import os
import sys
from typing import Any, Dict, List, Optional

from repro.core.telemetry.recorder import (DEFAULT_CAPACITY, NOOP_SPAN,
                                           Recorder, RingLog, SCHEMA,
                                           safe_attrs)
from repro.core.telemetry.export import (metrics_snapshot, read_events,
                                         write_jsonl)
from repro.core.telemetry.summarize import (format_summary, percentile,
                                            summarize_events, summarize_file)

__all__ = [
    "SCHEMA", "ENV", "CAP_ENV", "Recorder", "RingLog", "configure",
    "enabled", "recorder", "span", "instant", "counter", "snapshot",
    "reset", "safe_attrs", "write_jsonl", "read_events", "metrics_snapshot",
    "summarize_file", "summarize_events", "format_summary", "percentile",
    "DEFAULT_CAPACITY", "GC_SPAN",
]

ENV = "REPRO_TELEMETRY"
CAP_ENV = "REPRO_TELEMETRY_CAP"
#: profiler span around each garbage collection
GC_SPAN = "python.gc"

_recorder: Optional[Recorder] = None      # None <=> disabled fast path
_jsonl_path: Optional[str] = None
#: ``jax.profiler.TraceAnnotation`` once ``jax.profiler`` is imported
_annotation: Any = None


def configure(mode: Optional[str] = None,
              capacity: Optional[int] = None) -> Optional[Recorder]:
    """(Re)configure global telemetry; returns the active recorder or None.

    ``mode`` follows the env contract: ``"off"``/``""``/None disables,
    ``"on"`` records in memory, ``"jsonl:<path>"`` records and flushes the
    JSONL log at interpreter exit (or on :func:`flush`).  Reconfiguring
    replaces the recorder (prior events are dropped — snapshot first).
    """
    global _recorder, _jsonl_path
    mode = (mode or "off").strip()
    if mode.lower() in ("", "off", "0", "false"):
        _recorder, _jsonl_path = None, None
        return None
    if capacity is None:
        capacity = int(os.environ.get(CAP_ENV, DEFAULT_CAPACITY))
    path: Optional[str] = None
    if mode.lower().startswith("jsonl:"):
        path = mode[len("jsonl:"):]
        if not path:
            raise ValueError(f"{ENV}=jsonl:<path> needs a path")
    elif mode.lower() not in ("on", "1", "true"):
        raise ValueError(
            f"bad {ENV} value {mode!r}: expected off|on|jsonl:<path>")
    _recorder = Recorder(capacity=capacity)
    _jsonl_path = path
    from repro.core.telemetry import jaxmon
    jaxmon.install()
    return _recorder


def enabled() -> bool:
    return _recorder is not None


def recorder() -> Optional[Recorder]:
    """The active recorder (None when disabled)."""
    return _recorder


# ---- recording fast paths ------------------------------------------------
def _find_annotation() -> Any:
    """``jax.profiler.TraceAnnotation`` if ``jax.profiler`` has been
    imported (never imports it: a gc callback may run mid-import)."""
    global _annotation
    _annotation = getattr(sys.modules.get("jax.profiler"),
                          "TraceAnnotation", None)
    return _annotation


def span(name: str, proc: str = "main", **attrs: Any):
    """Context manager around one operation, recorded in every active
    sink: the ring, and the profiler's trace while a session collects."""
    ann = _annotation or _find_annotation()
    if ann is not None and not ann.is_enabled():
        ann = None
    rec = _recorder
    if rec is None:
        return NOOP_SPAN if ann is None else ann(name, **attrs)
    return rec.span(name, proc=proc,
                    annotation=None if ann is None else ann(name, **attrs),
                    **attrs)


def instant(name: str, proc: str = "main", **attrs: Any) -> None:
    rec = _recorder
    if rec is not None:
        rec.instant(name, proc=proc, **attrs)


def counter(name: str, value: float = 1.0, proc: str = "main") -> None:
    rec = _recorder
    if rec is not None:
        rec.counter(name, value, proc=proc)


def snapshot() -> Dict[str, Any]:
    """Metrics snapshot of the active recorder ({} when disabled)."""
    rec = _recorder
    return rec.snapshot() if rec is not None else {}


def events() -> List[Dict[str, Any]]:
    rec = _recorder
    return rec.event_list() if rec is not None else []


def reset() -> None:
    """Clear the active recorder's events and aggregates (keep recording)."""
    rec = _recorder
    if rec is not None:
        rec.clear()


def flush(path: Optional[str] = None) -> Optional[str]:
    """Write the JSONL log now (to ``path`` or the ``jsonl:`` env path)."""
    rec = _recorder
    target = path or _jsonl_path
    if rec is None or target is None:
        return None
    write_jsonl(target, rec)
    return target


_gc_span: Any = None     # the open python.gc annotation, between callbacks


def _gc_callback(phase: str, info: Dict[str, Any]) -> None:
    """Bracket each collection in a ``python.gc`` profiler span, so that a
    collector pause in a host loop shows as what held the device idle."""
    global _gc_span
    if phase == "start":
        ann = _annotation or _find_annotation()
        if ann is not None and ann.is_enabled():
            _gc_span = ann(GC_SPAN, generation=info["generation"])
            _gc_span.__enter__()
    elif _gc_span is not None:
        opened, _gc_span = _gc_span, None
        opened.__exit__(None, None, None)


gc.callbacks.append(_gc_callback)


@atexit.register
def _flush_at_exit() -> None:  # pragma: no cover - exercised via subprocess
    try:
        flush()
    except Exception:
        pass


# env bootstrap: a bad value must fail loudly at import, not silently
# record nothing while the user thinks they are tracing
configure(os.environ.get(ENV))
