"""The trace reduction on a small trace recorded on a v5e chip: the
stencil at L=128 under a jit, then a two-layer server (two prefills of two
buckets, decode steps), the server's run inside a host span."""

import harness
import trace_reduce

TRACE = str(harness.BENCH / "tests" / "data" / "probe.xplane.pb")


def test_names():
    assert trace_reduce.module_name("jit_decode_fn(1642153307)") == \
        "jit_decode_fn"
    assert trace_reduce.op_name(
        "%decode_pallas.7 = bf16[4,2,2,128]{3,2,1,0} custom-call(...)") == \
        "decode_pallas"
    assert trace_reduce.op_name("%fusion.110 = f32[4]{0} fusion(...)") == \
        "fusion"
    assert trace_reduce.op_name("%broadcast.23.clone = f32[] x") == \
        "broadcast"


def test_reduce_the_recorded_trace():
    s = trace_reduce.reduce(TRACE, window="bench.serve_window")
    assert s["chips"] == 1
    assert 0.0 < s["busy_s"] < s["window_s"]
    mods = s["modules"]
    assert mods["jit_prefill_fn"]["count"] == 4
    assert mods["jit_decode_fn"]["count"] == 5
    assert "laplacian_pallas" not in s["ops"]       # before the window
    assert s["ops"]["decode_pallas"]["count"] == 10  # 5 steps x 2 layers
    assert s["ops"]["flash_pallas"]["count"] == 8    # 4 prefills x 2 layers
    idle = sum(v for _, v in s["idle_gaps"])
    assert abs(idle - (s["window_s"] - s["busy_s"])) < 1e-9
    assert any("jit_decode_fn -> jit_decode_fn" in k for k, _ in
               s["idle_gaps"])
    assert [n for n, _ in s["device_ops"]][:1] == ["fusion"]
