"""BabelStream Pallas kernels vs pure-jnp oracle (interpret mode), with
shape/dtype sweeps and the paper's Eq. 2 byte model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metrics import babelstream_bytes
from repro.core.portable import registry
from repro.kernels.babelstream import ops, ref

SIZES = [128 * 512, 128 * 2048]
DTYPES = [jnp.float32]


def _data(rng, n, dtype):
    a = jnp.asarray(rng.standard_normal(n), dtype)
    b = jnp.asarray(rng.standard_normal(n), dtype)
    return a, b


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_elementwise_ops_match_oracle(rng, n, dtype):
    a, b = _data(rng, n, dtype)
    np.testing.assert_allclose(ops.copy_pallas(a, interpret=True),
                               ref.copy(a), rtol=1e-6)
    np.testing.assert_allclose(ops.mul_pallas(a, interpret=True),
                               ref.mul(a), rtol=1e-6)
    np.testing.assert_allclose(ops.add_pallas(a, b, interpret=True),
                               ref.add(a, b), rtol=1e-6)
    np.testing.assert_allclose(ops.triad_pallas(a, b, interpret=True),
                               ref.triad(a, b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", SIZES)
def test_dot_matches_oracle(rng, n):
    a, b = _data(rng, n, jnp.float32)
    got = ops.dot_pallas(a, b, interpret=True)
    want = ref.dot(a, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_dot_block_rows_sweep(rng):
    a, b = _data(rng, 128 * 1024, jnp.float32)
    want = ref.dot(a, b)
    for rows in (128, 256, 512):
        got = ops.dot_pallas(a, b, interpret=True, block_rows=rows)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_registry_backends_registered():
    for op in ("copy", "mul", "add", "triad", "dot"):
        k = registry.get(f"babelstream.{op}")
        assert {"xla", "pallas", "pallas_interpret"} <= set(k.backends)


def test_eq2_byte_model():
    # paper Eq. 2: copy/mul move 2 arrays, add/triad 3, dot 2
    n, isz = 1024, 4
    assert babelstream_bytes("copy", n, isz) == 2 * n * isz
    assert babelstream_bytes("add", n, isz) == 3 * n * isz
    assert babelstream_bytes("triad", n, isz) == 3 * n * isz
    assert babelstream_bytes("dot", n, isz) == 2 * n * isz
    with pytest.raises(ValueError):
        babelstream_bytes("nope", n, isz)


#: two (512, 128) tiles, so ``dot`` accumulates over two grid steps
CHAIN_N = 1 << 17


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_upstream_chain_through_registry(seed):
    """One upstream iteration (copy, mul, add, triad, dot, each reading
    what the one before wrote) through the registry's interpret backend,
    against the same chain in float64 NumPy."""
    r = np.random.default_rng(seed)
    a, b, c = (np.float32(s) * r.uniform(0.5, 1.5, CHAIN_N).astype(np.float32)
               for s in (0.1, 0.2, 0.3))
    s = ref.START_SCALAR

    def k(op, *args, **kw):
        return registry.get(f"babelstream.{op}")(
            *args, backend="pallas_interpret", **kw)
    c1 = k("copy", jnp.asarray(a))
    b1 = k("mul", c1, scalar=s)
    c2 = k("add", jnp.asarray(a), b1)
    a1 = k("triad", b1, c2, scalar=s)
    dot = k("dot", a1, b1)

    a64 = a.astype(np.float64)
    want_b = s * a64
    want_c = a64 + want_b
    want_a = want_b + s * want_c
    np.testing.assert_array_equal(np.asarray(c1), a)
    for got, want in ((b1, want_b), (c2, want_c), (a1, want_a)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    np.testing.assert_allclose(float(dot), np.dot(want_a, want_b), rtol=1e-5)
    assert not np.array_equal(np.asarray(c1), c)   # c was overwritten


@pytest.mark.parametrize("op", ["copy", "mul", "add", "triad", "dot"])
@pytest.mark.parametrize("block_rows,steps", [(512, 2), (256, 4)])
def test_tile_counter_fires_at_trace_time(op, block_rows, steps):
    """``babelstream.tile.<op>.<block_rows>`` adds the grid steps each time
    the kernel is traced."""
    from repro.core import telemetry as tel
    from repro.kernels.babelstream import kernel as K

    fn, n_in, takes_scalar = K.stream_2d_fns()[op]
    x = jax.ShapeDtypeStruct((CHAIN_N // K.LANES, K.LANES), jnp.float32)
    extra = (0.4,) if takes_scalar else ()
    tel.configure("on")
    try:
        jax.eval_shape(lambda *xs: fn(*xs, *extra, block_rows=block_rows),
                       *[x] * n_in)
        counters = {k: v for k, v in tel.snapshot()["counters"].items()
                    if k.startswith("babelstream.")}
    finally:
        tel.configure("off")
    assert counters == {f"babelstream.tile.{op}.{block_rows}": float(steps)}
