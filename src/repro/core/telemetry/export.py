"""Exporters: the JSONL event log and the metrics snapshot.

  * ``write_jsonl`` — the archival form.  Line 1 is a schema header, then
    one event object per line, then a footer carrying the aggregated
    counters and the ring-eviction count.  ``read_events`` reads it back.
  * ``Recorder.snapshot()`` (re-exported here as ``metrics_snapshot``) —
    the flat dict benchmarks embed in their ``BENCH_*.json`` artifacts.

A timeline view comes from the profiler instead: while a JAX profiler
session collects, every span also lands in its ``.xplane.pb`` beside the
device planes (see the package docstring), and Perfetto opens that trace.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional, Union

from repro.core.telemetry.recorder import Recorder, SCHEMA

EventSource = Union[Recorder, Iterable[Dict[str, Any]]]


def _events_of(source: EventSource) -> List[Dict[str, Any]]:
    if isinstance(source, Recorder):
        return source.event_list()
    return list(source)


def write_jsonl(path: str, source: EventSource,
                meta: Optional[Dict[str, Any]] = None,
                footer_data: Optional[Dict[str, Any]] = None) -> int:
    """Write header + events + footer; returns the number of event lines.

    ``footer_data`` overrides the footer aggregates — callers that drained
    a recorder's ring incrementally pass the recorder's final ``snapshot()``
    here so the counters still land in the file.
    """
    events = _events_of(source)
    header: Dict[str, Any] = {"schema": SCHEMA, "kind": "header"}
    footer: Dict[str, Any] = {"kind": "footer"}
    if isinstance(source, Recorder):
        header["t0_unix"] = source.epoch_unix
        snap = source.snapshot()
        footer.update(counters=snap["counters"],
                      events_dropped=snap["events_dropped"])
    if footer_data:
        footer.update({k: v for k, v in footer_data.items()
                       if k in ("counters", "events_dropped")})
    if meta:
        header.update(meta)
    with open(path, "w") as f:
        f.write(json.dumps(header, sort_keys=True) + "\n")
        for ev in events:
            f.write(json.dumps(ev, sort_keys=True) + "\n")
        f.write(json.dumps(footer, sort_keys=True) + "\n")
    return len(events)


def read_events(path: str) -> Dict[str, Any]:
    """Load a JSONL event log into ``{"header", "events", "footer"}``."""
    header: Dict[str, Any] = {}
    footer: Dict[str, Any] = {}
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            kind = obj.get("kind")
            if kind == "header":
                header = obj
            elif kind == "footer":
                footer = obj
            else:
                events.append(obj)
    return {"header": header, "events": events, "footer": footer}


def metrics_snapshot(recorder: Recorder) -> Dict[str, Any]:
    """Alias for ``Recorder.snapshot()`` so benchmark code imports one
    exporter module for both output forms."""
    return recorder.snapshot()
