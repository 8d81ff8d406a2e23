"""The BabelStream cell past the chip look, at a size the CPU holds: the
chain through the registry's interpret backend at 2^17 elements, so that
``dot`` accumulates over two grid steps.  A sound run reads ``correct``
true; each fault underneath the timed path reads it false."""

import dataclasses

import pytest

import harness

CELL = "babelstream-2p25"
#: two (512, 128) tiles of the registry's default
N = 1 << 17
LAST = 512 * 128


def tiny_stream(parts):
    cfg = dict(parts["config"], backend="pallas_interpret", n=N)
    return dict(parts, config=cfg)


def _patch(monkeypatch, op, wrap):
    import repro.kernels  # noqa: F401
    from repro.core.portable import get_kernel
    backends = get_kernel(f"babelstream.{op}").backends
    be = backends["pallas_interpret"]
    monkeypatch.setitem(backends, "pallas_interpret",
                        dataclasses.replace(be, fn=wrap(be.fn)))


def test_stream_sound(run_cell, capsys):
    out = run_cell(CELL, tiny_stream, capsys=capsys)
    assert out["correct"] is True and out["attempted"] > 0
    assert out["metrics"]["call_ms"]["value"] > 0
    assert set(out["checks"]) == {"stream_rel_err", "dot_rel_err"}


FAULTS = {
    # one triad element off by one
    "triad_element": ("triad", "stream_rel_err", lambda fn: (
        lambda b, c, **kw: fn(b, c, **kw).at[1000].add(1.0))),
    # dot without its last grid step's partial
    "dot_last_step": ("dot", "dot_rel_err", lambda fn: (
        lambda a, b, **kw: fn(a[:-LAST], b[:-LAST], **kw))),
    # mul with scalar 0.5 in place of the configured 0.4
    "mul_scalar": ("mul", "stream_rel_err", lambda fn: (
        lambda c, scalar=None, **kw: fn(c, scalar=0.5, **kw))),
    # a copy that writes zeros
    "copy_zeros": ("copy", "stream_rel_err", lambda fn: (
        lambda a, **kw: fn(a, **kw) * 0.0)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_stream_faults(run_cell, capsys, monkeypatch, fault):
    op, check, wrap = FAULTS[fault]
    _patch(monkeypatch, op, wrap)
    out = run_cell(CELL, tiny_stream, capsys=capsys)
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


def test_stream_eq2_bytes_at_2p25():
    counts = harness.load_module("counts", "babelstream")
    n = 1 << 25
    for op in ("copy", "mul", "dot"):
        assert counts.bytes_required(op, n, 4) == 268_435_456.0
    for op in ("add", "triad"):
        assert counts.bytes_required(op, n, 4) == 402_653_184.0


def test_stream_roofline_reads_its_module():
    """Each reader finds its own ``jit_bench_<op>`` module, and nothing in
    a trace without it (as a program that names modules otherwise)."""
    n, peak = 1 << 25, 819e9
    for op, arrays in (("copy", 2), ("triad", 3)):
        secs = arrays * n * 4 / peak * 2          # half the roofline, 10 calls
        r = {"trace": {"modules": {f"jit_bench_{op}":
                                   {"count": 10, "seconds": 10 * secs}}},
             "records": {"n": n, "itemsize": 4},
             "peaks": {"hbm_bytes_per_s": peak}}
        reader = harness.load_module("metrics", f"stream_{op}_roofline")
        assert reader.read(r) == pytest.approx(50.0)
        assert reader.read(dict(r, trace={"modules": {}})) is None


def test_stream_control_fails():
    """The chain in bfloat16, put in the program's place, fails a limit."""
    parts = tiny_stream(harness.cell(CELL))
    cfg = parts["config"]
    ref = harness.load_module("refs", cfg["reference"])
    inputs = ref.make_inputs(cfg, harness.seed_key(2 ** 31 + 7))
    got = ref.compare(cfg, inputs, ref.control(cfg, inputs))
    limits = parts["cell"]["limits"]
    assert any(got[k] > 10 * limits[k] for k in limits), got
