"""Core tests: portable registry, Phi metric (Eq. 4), HLO cost model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.hlo_analysis import parse_collective_bytes, parse_shape_bytes
from repro.core.hlo_cost import analyze_hlo
from repro.core.metrics import Efficiency, phi_bar
from repro.core.portable import PortableKernel


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
def test_portable_kernel_backend_selection_and_validation():
    k = PortableKernel(name="t", oracle="xla")
    k.add_backend("xla", lambda x: x * 2.0)
    k.add_backend("fast", lambda x: x + x)
    k.validate(jnp.ones(4), backend="fast")
    assert k.default_backend() == "xla"     # CPU host: no pallas
    with pytest.raises(KeyError):
        k.backend("missing")


def test_portable_kernel_fom():
    k = PortableKernel(name="t2", flops_model=lambda x: 100.0,
                       bytes_model=lambda x: 50.0)
    fom = k.figure_of_merit(1e-6, None)
    assert abs(fom["gflops_per_s"] - 0.1) < 1e-9
    assert abs(fom["gbytes_per_s"] - 0.05) < 1e-9


# --------------------------------------------------------------------------
# Eq. 4 — Phi metric
# --------------------------------------------------------------------------
def test_phi_bar_paper_table5_stencil():
    """Reproduce Table 5: stencil Phi = mean(0.82, 1.00, 0.87, 1.00) = 0.92."""
    terms = [Efficiency("H100", "fp32", 0.82, 1.0),
             Efficiency("MI300A", "fp32", 1.00, 1.0),
             Efficiency("H100", "fp64", 0.87, 1.0),
             Efficiency("MI300A", "fp64", 1.00, 1.0)]
    assert abs(phi_bar(terms) - 0.9225) < 1e-9


@settings(max_examples=100, deadline=None)
@given(effs=st.lists(st.floats(0.01, 10.0), min_size=1, max_size=10))
def test_phi_bar_bounded_by_extremes(effs):
    terms = [Efficiency("p", str(i), e, 1.0) for i, e in enumerate(effs)]
    phi = phi_bar(terms)
    assert min(effs) - 1e-9 <= phi <= max(effs) + 1e-9


def test_phi_bar_empty_raises():
    with pytest.raises(ValueError):
        phi_bar([])


# --------------------------------------------------------------------------
# HLO parsing / cost model
# --------------------------------------------------------------------------
def test_parse_shape_bytes():
    assert parse_shape_bytes("bf16[256,4096]{1,0}") == 256 * 4096 * 2
    assert parse_shape_bytes("(f32[8], s32[2])") == 8 * 4 + 2 * 4
    assert parse_shape_bytes("f32[]") == 4


def test_collective_parse_counts_kinds():
    hlo = """
ENTRY %main (p: f32[128]) -> f32[128] {
  %p = f32[128]{0} parameter(0)
  %ag = f32[256]{0} all-gather(%p), replica_groups={}
  %ar = f32[128]{0} all-reduce(%p), to_apply=%sum
  ROOT %out = f32[128]{0} copy(%ar)
}
"""
    stats = parse_collective_bytes(hlo)
    assert stats.count_by_kind["all-gather"] == 1
    assert stats.bytes_by_kind["all-gather"] == 256 * 4
    assert stats.bytes_by_kind["all-reduce"] == 128 * 4


def test_hlo_cost_scan_trip_count():
    def g(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return h
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(g).lower(x, w).compile()
    got = analyze_hlo(c.as_text())
    expect = 8 * 2 * 64 * 128 * 128
    assert 0.95 < got.flops / expect < 1.3
    assert got.unknown_trip_loops == 0


def test_hlo_cost_matches_xla_on_flat_program():
    def f(a, b):
        return jax.nn.gelu(a @ b) @ b.T
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    got = analyze_hlo(c.as_text())
    xla = c.cost_analysis()["flops"]
    assert abs(got.flops - xla) / xla < 0.2
