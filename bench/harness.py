"""What every cell of the benchmark shares: files found by name, seeds,
percentiles, the compile counter and the result line.

Nothing here imports JAX at module level, so the CPU tests and the
command's own start-up can use it before the accelerator is touched.
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys
import threading
from typing import Any, Dict, List, Optional, Sequence

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: that every run of a cell after the first finds its programs there
CACHE_DIR = ROOT / ".jax_cache"
#: profiler output of ``--trace 1`` runs, inside the checkout
TRACE_DIR = ROOT / ".bench_traces"


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module: systems, generators, refs,
    counts and metric readers are all found this way, by name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str, bench: Optional[Dict[str, Any]] = None
         ) -> Dict[str, Any]:
    """Everything one cell is made of, found by the names in
    ``BENCHMARK.json``: its entry, configuration, traffic mix and the cell's
    own file (rate, limits), plus the metrics it reports."""
    bench = bench or benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"unknown workload {workload!r}; have "
                       f"{sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[entry["config"]]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "entry": entry,
        "config": load_json(ROOT / conf["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "cell": load_json(BENCH / "workloads" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def seed_key(seed: int):
    """A JAX PRNG key from any whole number: the seed's low and high 32 bits
    are folded in separately, so seeds past 2**31 are fine."""
    import jax
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile over every value, linearly interpolated (numpy's
    default, as ``repro.serving.trace.latency_summary`` takes it)."""
    import numpy as np
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


class CompileCounter:
    """Counts JAX traces and backend compiles from ``jax.monitoring``.

    jax.monitoring has no way to remove a listener, so one counter is made
    per process and read as a difference around the window.
    """

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self) -> None:
        from jax import monitoring
        self._lock = threading.Lock()
        self.count = 0

        def on_duration(event: str, duration: float, **kw) -> None:
            if event in self.EVENTS:
                with self._lock:
                    self.count += 1

        monitoring.register_event_duration_secs_listener(on_duration)


def device_summary(chips: int) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them; raises SystemExit when
    there is no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform {dev.platform!r}, "
                         f"device_kind {dev.device_kind!r}, count "
                         f"{len(devices)}); nothing measured")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)} ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first ``n`` chips (0 where
    the backend keeps no statistics, as the CPU does in tests)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


def peaks(device_kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["chips"]:
        raise ValueError(f"no peaks for device_kind {device_kind!r}; known: "
                         f"{sorted(table['chips'])}")
    return table["chips"][device_kind]


def check_lines(checks: Dict[str, Dict[str, Any]]) -> List[str]:
    return [f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAIL'}" for name, c in checks.items()]


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                checks: Dict[str, Dict[str, Any]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return json.dumps(out)


def check(value: float, limit: float) -> Dict[str, Any]:
    """One compared number beside its limit: within it when ``value <=
    limit`` (a NaN is never within)."""
    return {"value": value, "limit": limit,
            "ok": bool(value == value and value <= limit)}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """One run of one cell: its arguments and files, the measured window,
    the compile counter and, with ``trace``, the profiler.

    A system calls :meth:`start_window` just before its first timed
    operation (everything before it is set-up), :meth:`end_window` when the
    window closes, :meth:`finish` once the last work the window started has
    ended, and :meth:`read_memory` before its reference runs.
    """

    def __init__(self, *, workload: str, seed: int, seconds: float,
                 trace: bool, parts: Dict[str, Any], t_start: float,
                 device: Dict[str, Any]) -> None:
        self.workload = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.parts = parts
        self.t_start = t_start
        self.device = device
        self.counter = CompileCounter()
        self.t0: Optional[float] = None
        self.t_end: Optional[float] = None
        self.compiles_in_window: Optional[int] = None
        self.trace_path: Optional[str] = None
        self.memory_peak: Optional[int] = None
        self._c0 = 0
        self._annotation = None
        self._profiling = False

    @property
    def config(self) -> Dict[str, Any]:
        return self.parts["config"]

    @property
    def traffic(self) -> Dict[str, Any]:
        return self.parts["traffic"]

    @property
    def cell(self) -> Dict[str, Any]:
        return self.parts["cell"]

    def start_profiler(self) -> None:
        """With ``trace``, start the profiler; a system may call this ahead
        of :meth:`start_window`, so that the profiler's own start-up does
        not stall the window's first work."""
        if self.trace and not self._profiling:
            import shutil
            import jax
            out = TRACE_DIR / self.workload
            shutil.rmtree(out, ignore_errors=True)
            out.mkdir(parents=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(out), profiler_options=opts)
            self._profiling = True

    def start_window(self) -> float:
        import time
        if self.trace:
            import jax
            self.start_profiler()
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        self._c0 = self.counter.count
        self.t0 = time.perf_counter()
        return self.t0

    def end_window(self, t_end: Optional[float] = None) -> float:
        import time
        self.t_end = time.perf_counter() if t_end is None else t_end
        self.compiles_in_window = self.counter.count - self._c0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        return self.t_end

    def finish(self) -> None:
        """Stop the profiler (if on) once the window's work has ended."""
        if self.trace and self.trace_path is None:
            import glob
            import jax
            jax.profiler.stop_trace()
            found = glob.glob(str(TRACE_DIR / self.workload / "**" /
                                  "*.xplane.pb"), recursive=True)
            if len(found) != 1:
                raise RuntimeError(f"expected one trace file, found {found}")
            self.trace_path = found[0]

    def read_memory(self) -> int:
        self.memory_peak = memory_peak_bytes(self.device["count"])
        return self.memory_peak

    @property
    def setup_s(self) -> float:
        return self.t0 - self.t_start
