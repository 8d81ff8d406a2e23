"""The shape-based counts and the peaks table, pinned to hand-worked
values.  None of them imports the program."""

import pathlib

import pytest

import harness

S7 = harness.load_module("counts", "stencil7")
G = harness.load_module("counts", "dense_gqa")
A = harness.load_module("counts", "attention")
GRANITE = harness.load_json(harness.BENCH / "configs" /
                            "granite-3.0-8b-16l.json")


def test_counts_import_nothing_of_the_program():
    for f in (harness.BENCH / "counts").glob("*.py"):
        imports = [line for line in f.read_text().splitlines()
                   if line.startswith(("import ", "from "))]
        assert not any("repro" in line or "jax" in line for line in imports)


def test_stencil_eq1_bytes_at_l512():
    # (512^3 - 8 - 12*510) * 4 + 510^3 * 4
    assert S7.bytes_required((512, 512, 512), 4) == 1_067_450_400.0


def test_granite_16_layers():
    w = G.widths(GRANITE)
    assert w == {"d": 4096, "h": 32, "kv": 8, "dh": 128, "f": 12800,
                 "v": 49155, "layers": 16}
    # per layer: q,o 2*4096*4096 + k,v 2*4096*1024 + 3*4096*12800
    assert G.layer_matmul_params(w) == 199_229_440
    assert G.params(w) == 16 * (199_229_440 + 8192) + 49155 * 4096 + 4096
    assert G.params(w) == 3_389_145_088
    assert G.params(dict(w, layers=40)) == 8_170_848_256   # published 8.17 B


def test_flops_and_bytes_by_hand():
    w = {"d": 8, "h": 2, "kv": 1, "dh": 4, "f": 16, "v": 10, "layers": 1}
    mm = 2 * 8 * 8 + 2 * 8 * 4 + 3 * 8 * 16          # 576
    assert G.layer_matmul_params(w) == mm
    assert G.decode_flops(w, 5) == 2 * mm + 4 * 2 * 4 * 5 + 2 * 8 * 10
    assert G.prefill_flops(w, 3) == 2 * mm * 3 + 4 * 2 * 4 * 6 + 2 * 8 * 10
    f, b = A.prefill(w, 3)
    assert f == 4 * 2 * 4 * 6 and b == (2 * 3 * 8 + 2 * 3 * 4) * 2
    f, b = A.decode(w, 5)
    assert f == 4 * 2 * 4 * 5 and b == (2 * 5 * 4 + 2 * 8) * 2
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert A.least_seconds(200.0, 10.0, peaks) == 2.0
    assert A.least_seconds(200.0, 50.0, peaks) == 5.0


def test_peaks_by_device_kind():
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in harness.load_json(harness.BENCH / "peaks.json")[
        "source"]
    with pytest.raises(ValueError):
        harness.peaks("TPU v9 imaginary")
