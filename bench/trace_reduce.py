"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle time inside the measured window, device
time per XLA module and per operation, and what the host was doing in each
idle gap.

What a TPU trace holds, as read by hand from one recorded on a v5e chip
(``bench/tests/data/probe.xplane.pb``):

* plane ``/device:TPU:<n>`` per chip, with lines ``XLA Modules`` (one event
  per program execution, named ``jit_<function>(<fingerprint>)``), ``XLA
  Ops`` (one event per HLO instruction run, named by the instruction's text,
  ``%<name>.<n> = <shape> <opcode>(...)``) and ``Async XLA Ops`` (copies and
  slices in flight, which overlap the ops and are not counted as busy).
  A Pallas kernel is a ``custom-call`` instruction named after the jitted
  function that wraps it (``%decode_pallas.7``, ``%flash_pallas.9``,
  ``%laplacian_pallas.1``).
* plane ``/host:CPU``, one line per host thread, holding the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (``bench.window``, ``bench.step``,
  ``bench.wait_arrival``).

Host and device events share one clock in the trace (to about a
millisecond), so the window is the ``bench.window`` span on the host.
"""

from __future__ import annotations

import collections
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
#: instructions that only contain other instructions: their time is that of
#: what runs inside them, so they are left out of the per-op table
CONTAINERS = {"while", "conditional", "call"}

_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")
_OP = re.compile(r"^%?([^\s=]+?)(\.\d+)*(\.clone)?(\s|$)")


def module_name(event_name: str) -> str:
    """``jit_decode_fn(16421533072606376251)`` -> ``jit_decode_fn``."""
    return _MODULE.match(event_name).group(1)


def op_name(event_name: str) -> str:
    """``%decode_pallas.7 = bf16[...] custom-call(...)`` -> ``decode_pallas``."""
    head = event_name.split(" = ", 1)[0].strip()
    m = _OP.match(head)
    return m.group(1) if m else head


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def host_spans(data, names) -> Dict[str, List[Tuple[float, float]]]:
    """Intervals (ns) of the host spans with these names."""
    out: Dict[str, List[Tuple[float, float]]] = {n: [] for n in names}
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns))
    return out


def reduce(path: str, *, window: str = "bench.window",
           host_labels=("bench.step", "bench.wait_arrival", "bench.call"),
           top: int = 10) -> Dict[str, Any]:
    """Summary of the trace inside the host span ``window``.

    Returns ``window_s``; ``busy_s`` (union of the ``XLA Ops`` intervals,
    averaged over the chips in the trace); per-module and per-op device
    seconds and counts (summed over chips); ``idle_gaps``, the idle seconds
    of chip 0 grouped by the host span around each gap and the modules on
    either side of it, largest first; and ``device_ops``, the ``top`` ops by
    device seconds.
    """
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans = host_spans(data, (window,) + tuple(host_labels))
    if len(spans[window]) != 1:
        raise ValueError(f"trace {path}: expected one {window!r} span, found "
                         f"{len(spans[window])}")
    w0, w1 = spans[window][0]

    chips: List[Dict[str, Any]] = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        ops: List[Tuple[float, float, str]] = []
        if "XLA Ops" in lines:
            for e in lines["XLA Ops"].events:
                c = _clip(e.start_ns, e.start_ns + e.duration_ns, w0, w1)
                if c:
                    ops.append((c[0], c[1], op_name(e.name)))
        mods: List[Tuple[float, float, str]] = []
        if "XLA Modules" in lines:
            for e in lines["XLA Modules"].events:
                c = _clip(e.start_ns, e.start_ns + e.duration_ns, w0, w1)
                if c:
                    mods.append((c[0], c[1], module_name(e.name)))
        if ops or mods:
            chips.append({"name": plane.name, "ops": ops, "modules": mods})
    if not chips:
        raise ValueError(f"trace {path}: no device operation in the window")

    modules: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"seconds": 0.0, "count": 0})
    ops_t: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"seconds": 0.0, "count": 0})
    busy_total = 0.0
    for chip in chips:
        for a, b, name in chip["modules"]:
            modules[name]["seconds"] += (b - a) * 1e-9
            modules[name]["count"] += 1
        for a, b, name in chip["ops"]:
            if name not in CONTAINERS:
                ops_t[name]["seconds"] += (b - a) * 1e-9
                ops_t[name]["count"] += 1
        busy = _union([(a, b) for a, b, _ in chip["ops"]])
        chip["busy"] = busy
        busy_total += sum(b - a for a, b in busy) * 1e-9

    gaps = _idle_gaps(chips[0], w0, w1, spans, host_labels)
    device_ops = sorted(((n, v["seconds"]) for n, v in ops_t.items()),
                        key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / len(chips),
        "chips": len(chips),
        "modules": dict(modules),
        "ops": dict(ops_t),
        "device_ops": [[n, s] for n, s in device_ops],
        "idle_gaps": gaps[:top],
    }


def _idle_gaps(chip, w0, w1, spans, host_labels) -> List[List[Any]]:
    """Idle seconds of one chip, grouped by label: the host span that holds
    the middle of each gap (or ``host``) and the modules on either side."""
    mods = sorted(chip["modules"])
    starts = [m[0] for m in mods]
    host = sorted((a, b, name) for name in host_labels
                  for a, b in spans[name])
    totals: Dict[str, float] = collections.defaultdict(float)
    prev_end = w0
    import bisect
    for a, b in chip["busy"] + [(w1, w1)]:
        if a > prev_end:
            mid = 0.5 * (prev_end + a)
            i = bisect.bisect_right(starts, mid)
            before = mods[i - 1][2] if i > 0 else "start"
            after = mods[i][2] if i < len(mods) else "end"
            where = _holder(host, mid) or "host"
            totals[f"{where}: {before} -> {after}"] += (a - prev_end) * 1e-9
        prev_end = max(prev_end, b)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])]


def _holder(host: List[Tuple[float, float, str]], t: float) -> Optional[str]:
    """Innermost (latest-starting) host span that holds time ``t``."""
    best = None
    for a, b, name in host:
        if a > t:
            break
        if b >= t:
            best = name
    return best
