"""Seven-point stencil Pallas kernel vs oracle + property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.metrics import stencil7_effective_bytes
from repro.kernels.stencil7 import ops, ref


def _pencil_padded(rng, nz, ny_local, nx, by):
    """A pencil composite's local block: one halo row each side, then dead
    zero rows up to a multiple of ``by``."""
    u = rng.standard_normal((nz, ny_local + 2, nx))
    extra = (-(ny_local + 2)) % by
    return np.concatenate([u, np.zeros((nz, extra, nx))], axis=1)


@pytest.mark.parametrize("shape,by", [
    ((8, 16, 128), 8), ((6, 32, 256), 16), ((4, 8, 128), 4),
    ((12, 24, 128), 8),
    # whole plane, chosen by shape (by=None)
    ((6, 32, 256), None),
    # row tiles, chosen by shape: a 4 MiB plane overflows the VMEM budget
    ((3, 1024, 1024), None),
    # nz of 1, 3 and an odd count, on both paths
    ((1, 16, 128), None), ((1, 32, 128), 8),
    ((3, 16, 128), None), ((3, 32, 128), 16),
    ((7, 40, 128), None), ((7, 40, 128), 8),
    # ny padded up to a multiple of by, as the pencil composite pads
    ("pencil", 8), ("pencil", 16),
], ids=lambda v: "default" if v is None else str(v))
def test_matches_oracle_fp32(rng, shape, by):
    if shape == "pencil":
        u = jnp.asarray(_pencil_padded(rng, 5, 12, 128, by), jnp.float32)
    else:
        u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    coeffs = ref.default_coefficients(1.0, 2.0, 3.0)
    got = ops.laplacian_pallas(u, *coeffs, by=by, interpret=True)
    want = ops.laplacian_xla(u, *coeffs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,by,path", [
    ((512, 512, 512), 512, "plane"),       # the paper's L=512: 6 MiB window
    ((1024, 1024, 1024), 512, "rows"),     # L=1024: the plane overflows
    ((8, 16, 128), 16, "plane"),
])
def test_default_tile_from_shape(shape, by, path):
    """``by=None`` takes the whole plane where the window fits the VMEM
    budget, else the largest declared height that fits; the trace-time
    counter names the path the shape took."""
    from repro.core import telemetry as tel
    from repro.kernels.stencil7 import kernel as K

    assert K.default_by(shape, 4) == by
    assert K.vmem_working_set_bytes(shape, 4, by) <= K.VMEM_BUDGET
    if path == "rows":
        assert K.vmem_working_set_bytes(shape, 4, shape[1]) > K.VMEM_BUDGET
    tel.configure("on")
    try:
        jax.eval_shape(lambda u: K.laplacian_3d(u, 1.0, 1.0, 1.0, -6.0),
                       jax.ShapeDtypeStruct(shape, jnp.float32))
        counters = {k: v for k, v in tel.snapshot()["counters"].items()
                    if k.startswith("stencil7.")}
    finally:
        tel.configure("off")
    assert counters == {f"stencil7.tile.{path}": 1.0}


def test_boundary_zero(rng):
    u = jnp.asarray(rng.standard_normal((8, 16, 128)), jnp.float32)
    out = np.asarray(ops.laplacian_pallas(u, by=8, interpret=True))
    assert (out[0] == 0).all() and (out[-1] == 0).all()
    assert (out[:, 0] == 0).all() and (out[:, -1] == 0).all()
    assert (out[:, :, 0] == 0).all() and (out[:, :, -1] == 0).all()


@settings(max_examples=10, deadline=None)
@given(scale=st.floats(-4.0, 4.0))
def test_linearity(scale):
    """Laplacian is linear: L(a*u) == a*L(u)."""
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.standard_normal((4, 8, 128)), jnp.float32)
    l1 = ops.laplacian_xla(u * scale)
    l2 = ops.laplacian_xla(u) * scale
    np.testing.assert_allclose(l1, l2, rtol=1e-3, atol=1e-3)


def test_constant_field_interior_zero():
    """Laplacian of a constant field vanishes on the interior."""
    u = jnp.ones((6, 8, 128), jnp.float32)
    out = np.asarray(ops.laplacian_pallas(u, by=8, interpret=True))
    np.testing.assert_allclose(out[1:-1, 1:-1, 1:-1], 0.0, atol=1e-4)


def test_eq1_byte_model():
    # paper Eq. 1
    L, isz = 512, 8
    fetch = (L ** 3 - 8 - 12 * (L - 2)) * isz
    write = (L - 2) ** 3 * isz
    assert stencil7_effective_bytes(L, isz) == fetch + write
