"""Every cell of BENCHMARK.json is found by name, and the file keeps to the
shape the harness reads."""

import re

import pytest

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_workload_loads_by_name():
    bench = harness.benchmark()
    for w in bench["workloads"]:
        parts = harness.cell(w["name"], bench)
        cfg = parts["config"]
        harness.load_module("systems", cfg["system"])
        harness.load_module("refs", cfg["reference"])
        harness.load_module("generators", parts["traffic"]["generator"])
        assert parts["end_to_end"][0]["name"] == "setup_s"
        assert len(parts["end_to_end"]) >= 2 and parts["per_layer"]
        for m in parts["per_layer"]:
            assert callable(harness.load_module("metrics", m["name"]).read)


def test_names_units_and_arrows():
    bench = harness.benchmark()
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_configs_list_what_they_reduced():
    bench = harness.benchmark()
    for c in bench["configs"]:
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and key in cfg["published"]
        for key, d in cfg.get("departures", {}).items():
            assert key in cfg and key not in c["reduced"]
            assert d["as_run"] != cfg[key] and d["why"]


def test_departures_are_what_the_reference_follows():
    cfg = harness.load_json(harness.BENCH / "configs" /
                            "granite-3.0-8b-16l.json")
    ref = harness.load_module("refs", cfg["reference"])
    assert ref.as_run(cfg)["rms_norm_eps"] == 1e-6
    assert cfg["embedding_multiplier"] == 12.0        # published, kept
    bad = dict(cfg, departures={k: v for k, v in cfg["departures"].items()
                                if k != "residual_multiplier"})
    with pytest.raises(ValueError):
        ref.as_run(bad)
