"""Host time per decode step outside its wait for the chip (serving cells).

Mean, over the engine's ``serving.decode_step`` spans that start inside the
traced window, of the span's duration less that of its
``serving.decode.wait`` child: the uploads, the dispatch and the per-slot
loop that the host runs in each step while the chip has nothing to do
(program spans of ``repro.serving.engine``, on the trace's ``/host:CPU``
plane).

This file also holds what the engine-span readers share, and
``admit_host_ms`` loads it by name: the program's spans of the run's trace,
parsed once, and a log of the window's device idle seconds by the program
span that holds them.
"""

import bisect
import collections
import glob
import math
import os
import warnings

import harness
import trace_reduce

#: host spans of the program, by name prefix
PROGRAM = ("serving.", "python.gc")
WINDOW = "bench.window"
#: trace path -> what ``_read`` found in it; a run parses its trace once
_FOUND = {}


def newest_trace():
    """The newest ``.xplane.pb`` under the benchmark's trace directory: the
    one this run has just written."""
    found = glob.glob(str(harness.TRACE_DIR / "**" / "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def program_spans(r):
    """The program's spans that overlap the window of the run's trace, as
    ``{"window": (start_ns, end_ns), "spans": [(start_ns, end_ns, name,
    line)]}``; None without a trace, or where the trace's window is not the
    one ``r`` was reduced from (by more than 1 us)."""
    path = newest_trace()
    if path is None:
        return None
    if path not in _FOUND:
        _FOUND[path] = _read(path, r)
    found = _FOUND[path]
    if found is None or abs(found["window_s"]
                            - r["trace"]["window_s"]) > 1e-6:
        return None
    return found


def host_ms(found, outer, wait):
    """Mean ms, over the ``outer`` spans that start inside the window, of
    each span's duration less that of the ``wait`` spans inside it on its
    own thread; None where no ``outer`` span started in the window."""
    w0, w1 = found["window"]
    waits = collections.defaultdict(list)        # line -> start-sorted
    for a, b, name, line in found["spans"]:
        if name == wait:
            waits[line].append((a, b))
    own = []
    for a, b, name, line in found["spans"]:
        if name == outer and w0 <= a <= w1:
            ws = waits[line]
            inside = ws[bisect.bisect_left(ws, (a,)):
                        bisect.bisect_right(ws, (b, math.inf))]
            own.append((b - a) - sum(d - c for c, d in inside if d <= b))
    return 1e-6 * sum(own) / len(own) if own else None


def read(r):
    found = program_spans(r)
    if found is None:
        return None
    return host_ms(found, "serving.decode_step", "serving.decode.wait")


def _read(path, r):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    window, spans, bench = [], [], []
    device = None           # the first chip's XLA Ops and XLA Modules
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if plane.name == trace_reduce.HOST_PLANE:
                for i, line in enumerate(plane.lines):
                    for e in line.events:
                        iv = (e.start_ns, e.start_ns + e.duration_ns,
                              e.name, i)
                        if e.name == WINDOW:
                            window.append(iv)
                        elif e.name.startswith(PROGRAM):
                            spans.append(iv)
                        elif e.name.startswith("bench."):
                            bench.append(iv)
            elif (plane.name.startswith(trace_reduce.DEVICE_PREFIX)
                  and device is None):
                lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                      e.name, 0) for e in line.events]
                         for line in plane.lines}
                if lines.get("XLA Ops"):
                    device = lines
    if len(window) != 1:
        harness.log(f"program spans: {len(window)} {WINDOW} spans in {path}")
        return None
    w0, w1 = window[0][:2]
    keep = sorted(s for s in spans if s[0] <= w1 and s[1] >= w0)
    found = {"window": (w0, w1), "window_s": (w1 - w0) * 1e-9,
             "spans": keep}
    device = device or {}
    _log(found, sorted(s for s in bench if s[0] <= w1 and s[1] >= w0),
         [iv[:2] for iv in device.get("XLA Ops", [])],
         sorted(device.get("XLA Modules", [])), r)
    return found


def _idle_gaps(busy, w0, w1):
    clipped = [c for c in (trace_reduce._clip(a, b, w0, w1) for a, b in busy)
               if c]
    gaps, prev = [], w0
    for a, b in trace_reduce._union(clipped) + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def _holders(spans, times):
    """For each of the ascending ``times``, the name of the latest-starting
    of the start-sorted ``spans`` that holds it, or None."""
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        while active and active[-1][1] < t:    # ended: never holds again
            active.pop()
        out.append(active[-1][2] if active else None)
    return out


def _log(found, bench, busy, modules, r):
    """One table on stderr: the window's device idle seconds by the
    innermost program span that holds each gap's midpoint (``bench.*`` or
    ``host`` where none does), with the part of them whose midpoint lies
    inside a device program (``XLA Modules``); then each program span's
    count and mean duration over the window."""
    w0, w1 = found["window"]
    gaps = _idle_gaps(busy, w0, w1)
    mids = [0.5 * (a + b) for a, b in gaps]
    where = [p or b or "host" for p, b in zip(_holders(found["spans"], mids),
                                             _holders(bench, mids))]
    idle = collections.defaultdict(lambda: [0.0, 0, 0.0])
    for (a, b), name, mod in zip(gaps, where, _holders(modules, mids)):
        idle[name][0] += (b - a) * 1e-9
        idle[name][1] += 1
        idle[name][2] += (b - a) * 1e-9 if mod else 0.0
    total = sum(v[0] for v in idle.values())
    program = sum(v[0] for k, v in idle.items() if k.startswith(PROGRAM))
    rows = [f"  {k:32s} {s:12.6f} s {100 * s / total:7.3f} % {n:7d} gaps "
            f"{inside:12.6f} s inside a program"
            for k, (s, n, inside) in sorted(idle.items(),
                                            key=lambda kv: -kv[1][0])]
    per_span = collections.defaultdict(lambda: [0, 0.0])
    for a, b, n, _ in found["spans"]:
        if w0 <= a <= w1:
            per_span[n][0] += 1
            per_span[n][1] += (b - a) * 1e-6
    spans = [f"  {n:32s} {c:7d} x {ms / c:10.4f} ms"
             for n, (c, ms) in sorted(per_span.items())]
    counts = {k: v["count"] for k, v in r["trace"].get("modules", {}).items()}
    share = 100 * program / total if total else math.nan
    harness.log("\n".join(
        [f"device idle by program span: {total!r} s idle in a "
         f"{found['window_s']!r} s window, {share:.3f} % inside "
         f"{'/'.join(PROGRAM)}* spans"] + rows +
        [f"program spans starting in the window (modules {counts}):"] +
        spans))
