"""Record a small device trace of the benchmark's two paths and print its
planes, lines and event names.

    python bench/tools/record_trace.py OUT_DIR

Runs the seven-point stencil at L=128 under a benchmark-named jit and a
small dense GQA server (two layers, head_dim 128, four slots) on one TPU,
traces both, copies the ``.xplane.pb`` to OUT_DIR and prints, per plane
and line, the number of events and the most frequent event names with
their stats.  The copied trace is the fixture of ``bench/tests``.
"""

from __future__ import annotations

import collections
import glob
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: no TPU")
    import repro.kernels  # noqa: F401
    from repro.configs.base import ModelConfig
    from repro.core.portable import get_kernel
    from repro.models import transformer as T
    from repro.serving import Request, ServingEngine

    stencil = get_kernel("stencil7")
    bench_stencil7 = jax.jit(lambda u: stencil(u, backend="pallas"))
    u = jax.random.normal(jax.random.PRNGKey(1), (128, 128, 128), jnp.float32)
    jax.block_until_ready(bench_stencil7(u))

    cfg = ModelConfig(name="probe", family="dense", n_layers=2, d_model=512,
                      n_heads=4, n_kv_heads=2, d_ff=1024, vocab_size=1000,
                      head_dim=128, param_dtype="bfloat16",
                      tie_embeddings=True)
    params = jax.jit(T.init_params, static_argnums=0)(cfg,
                                                      jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, num_slots=4, cache_len=512,
                        prefill_buckets=(128, 256), attn_backend="pallas")
    rng = np.random.default_rng(0)

    def reqs(base):
        return [Request(uid=base + i, prompt=rng.integers(
            2, 1000, int(n)).astype(np.int32), max_new_tokens=6)
            for i, n in enumerate((100, 200, 50, 250))]
    eng.run(reqs(0))                                  # compile
    logdir = tempfile.mkdtemp(prefix="probe_trace_")
    with jax.profiler.trace(logdir):
        for _ in range(3):
            jax.block_until_ready(bench_stencil7(u))
        with jax.profiler.TraceAnnotation("bench.serve_window"):
            eng.run(reqs(100))
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "probe.xplane.pb"))
    print(f"trace {path}: {os.path.getsize(path)} bytes")

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            print(f"  LINE {line.name!r} events={len(events)}")
            shown = 0
            for name, n in names.most_common(25):
                ev = next(e for e in events if e.name == name)
                stats = {k: (str(v)[:80]) for k, v in ev.stats}
                print(f"    {n:5d} x {name[:100]!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns} stats={stats}")
                shown += 1
    shutil.rmtree(logdir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
