"""CPU tests of the benchmark's pieces: ``python -m pytest bench/tests``.

The cells' own sizes need the chip; here each piece runs at a size the CPU
holds, with the Pallas kernels in interpret mode or the XLA attention path.
"""

import os
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny_serving(parts, backend="xla"):
    """A serving cell's parts at a size the CPU holds: the cell's own
    limits and traffic shape, a two-layer model of the same layout."""
    cfg = dict(parts["config"], hidden_size=1024, intermediate_size=2048,
               num_attention_heads=8, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=2048)
    cfg["engine"] = dict(cfg["engine"], slots=4, cache_len=256,
                         prefill_buckets=[64, 128], attn_backend=backend)
    traffic = dict(parts["traffic"],
                   prompt=dict(median=40, sigma=0.8, min=4, max=128),
                   output=dict(median=16, sigma=0.5, min=4, max=48))
    return dict(parts, config=cfg, traffic=traffic,
                cell=dict(parts["cell"], rate_rps=4.0, preroll_s=2.0))


def tiny_stencil(parts):
    cfg = dict(parts["config"], backend="pallas_interpret",
               shape=[8, 64, 128])
    return dict(parts, config=cfg)


@pytest.fixture
def run_cell(monkeypatch):
    """Run ``bench/run.py`` in process on the CPU, past the chip look, with
    the cell's parts shrunk by ``shrink``; returns the result line."""
    import json
    import harness
    import run

    def go(workload, shrink, seconds=2.0, capsys=None):
        orig = harness.cell
        monkeypatch.setattr(run, "setup_jax", lambda: None)
        monkeypatch.setattr(harness, "cell",
                            lambda w, bench=None: shrink(orig(w, bench)))
        rc = run.main(["--workload", workload, "--seed", "3000000011",
                       "--seconds", str(seconds), "--trace", "0"],
                      device=CPU)
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go
