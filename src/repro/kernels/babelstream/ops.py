"""jit'd public wrappers for BabelStream; registers backends in the registry.

All wrappers take flat 1-D arrays (like the benchmark) and handle the
(n/128, 128) reshape + padding internally.  Three backends:
``xla`` (ref oracle), ``pallas`` (TPU target), ``pallas_interpret`` (CPU CI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.portable import on_tpu, register_kernel
from repro.core.metrics import babelstream_bytes
from repro.kernels.babelstream import kernel as K
from repro.kernels.babelstream import ref

LANES = K.LANES


def _as2d(x):
    n = x.shape[0]
    if n % LANES:
        raise ValueError(f"BabelStream size must be a multiple of {LANES}")
    return x.reshape(n // LANES, LANES)


def _flat(x2):
    return x2.reshape(-1)


# One jitted wrapper per op, each under its own name: the trace names a
# Pallas kernel after the jitted function around it.
@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def copy_pallas(a, *, interpret=False, block_rows=K.BLOCK_ROWS):
    return _flat(K.copy_2d(_as2d(a), interpret=interpret,
                           block_rows=block_rows))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def add_pallas(a, b, *, interpret=False, block_rows=K.BLOCK_ROWS):
    return _flat(K.add_2d(_as2d(a), _as2d(b), interpret=interpret,
                          block_rows=block_rows))


@functools.partial(jax.jit,
                   static_argnames=("scalar", "interpret", "block_rows"))
def mul_pallas(c, scalar=ref.START_SCALAR, *, interpret=False,
               block_rows=K.BLOCK_ROWS):
    return _flat(K.mul_2d(_as2d(c), scalar, interpret=interpret,
                          block_rows=block_rows))


@functools.partial(jax.jit,
                   static_argnames=("scalar", "interpret", "block_rows"))
def triad_pallas(b, c, scalar=ref.START_SCALAR, *, interpret=False,
                 block_rows=K.BLOCK_ROWS):
    return _flat(K.triad_2d(_as2d(b), _as2d(c), scalar, interpret=interpret,
                            block_rows=block_rows))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def dot_pallas(a, b, *, interpret=False, block_rows=K.BLOCK_ROWS):
    return K.dot_2d(_as2d(a), _as2d(b), interpret=interpret,
                    block_rows=block_rows)


# ---- registry ------------------------------------------------------------
def _bytes_model_factory(op):
    def model(*arrays, **kw):
        return babelstream_bytes(op, arrays[0].size, arrays[0].dtype.itemsize)
    return model


_JIT_REF = {name: jax.jit(getattr(ref, name))
            for name in ("copy", "mul", "add", "triad", "dot")}

_PALLAS = {"copy": copy_pallas, "mul": mul_pallas, "add": add_pallas,
           "triad": triad_pallas, "dot": dot_pallas}

def _block_rows_ok(p, *arrays, **kw):
    # the 1-D grid requires n to tile into (block_rows, LANES) blocks exactly
    return arrays[0].size % (p["block_rows"] * LANES) == 0


for _op in ("copy", "mul", "add", "triad", "dot"):
    _k = register_kernel(
        f"babelstream.{_op}",
        bytes_model=_bytes_model_factory(_op),
        doc=f"BabelStream {_op} (paper Eq. 2 FoM)")
    _k.add_backend("xla", _JIT_REF[_op])
    _k.add_backend("pallas", _PALLAS[_op], available=on_tpu)
    _k.add_backend(
        "pallas_interpret",
        functools.partial(_PALLAS[_op], interpret=True))
    _k.declare_tunables(("pallas", "pallas_interpret"),
                        block_rows=K.BLOCK_ROWS_GRID,
                        constraint=_block_rows_ok)
    if _op == "dot":
        # dot reduces every grid step into the same (1, 1) output block —
        # a declared sequential accumulator, not a write race
        _k.declare_grid_contract(("pallas", "pallas_interpret"),
                                 accumulator_outputs=(0,))
    # streaming kernels by construction: O(1) flops per byte, memory-bound
    # on every chip ridge the auditor models
    _k.declare_roofline_contract(("xla", "pallas", "pallas_interpret"),
                                 bound="memory")
