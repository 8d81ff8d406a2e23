"""BabelStream Pallas-TPU kernels.

TPU adaptation (DESIGN.md §3): the four streaming ops are 1-D grids over
(BLOCK, 128)-shaped VMEM tiles (VPU-aligned).  Dot replaces the paper's
block-shared-memory tree reduction + host reduction with the TPU-idiomatic
sequential-grid accumulation: the output BlockSpec maps every grid step onto
the same (1,1) block, which lives in VMEM for the whole grid and is
zero-initialised on the first step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import telemetry as tel

# Rows per VMEM tile. 512x128 f32 = 256 KiB/operand — comfortably inside
# VMEM next to double-buffering, and a multiple of the (8,128) vreg.
BLOCK_ROWS = 512
LANES = 128
#: declared row-tile grid (ops.py registers it; sharded composites reuse it)
BLOCK_ROWS_GRID = (128, 256, 512, 1024)


def local_block_rows(n_local: int, block_rows: Optional[int] = None) -> int:
    """Row tile for a (possibly sharded) local 1-D block of ``n_local``
    elements.  An explicit ``block_rows`` is validated against the local
    extent (the grid must tile ``(n_local/128, 128)`` exactly); ``None``
    picks the largest declared tile that fits."""
    if block_rows is not None:
        if n_local % (block_rows * LANES):
            raise ValueError(
                f"block_rows={block_rows} does not tile the local extent "
                f"{n_local} into ({block_rows}, {LANES}) blocks")
        return block_rows
    for cand in sorted(BLOCK_ROWS_GRID, reverse=True):
        if n_local % (cand * LANES) == 0:
            return cand
    raise ValueError(
        f"no declared row tile {BLOCK_ROWS_GRID} tiles the local extent "
        f"{n_local}")


def _grid_1d(n: int, block_rows: int) -> int:
    per_block = block_rows * LANES
    if n % per_block:
        raise ValueError(f"size {n} not a multiple of {per_block}; "
                         "pad at the ops.py layer")
    return n // per_block


def _tile(i):
    return (i, 0)


def _traced_grid(op: str, n: int, block_rows: int) -> int:
    """Grid steps of ``op`` over ``n`` elements, counted at trace time as
    ``babelstream.tile.<op>.<block_rows>`` (the value adds the steps)."""
    steps = _grid_1d(n, block_rows)
    tel.counter(f"babelstream.tile.{op}.{block_rows}", steps,
                proc="dispatch")
    return steps


def _elementwise_call(op, body, n, dtype, n_in, block_rows, interpret):
    spec = pl.BlockSpec((block_rows, LANES), _tile)
    return pl.pallas_call(
        body,
        grid=(_traced_grid(op, n, block_rows),),
        in_specs=[spec] * n_in,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), dtype),
        interpret=interpret,
    )


# ---- kernel bodies -------------------------------------------------------
def _copy_body(a_ref, o_ref):
    o_ref[...] = a_ref[...]


def _mul_body(scalar, c_ref, o_ref):
    o_ref[...] = scalar * c_ref[...]


def _add_body(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


def _triad_body(scalar, b_ref, c_ref, o_ref):
    o_ref[...] = b_ref[...] + scalar * c_ref[...]


def _dot_body(a_ref, b_ref, o_ref, *, acc_dtype):
    # Sequential-grid accumulation: o_ref is the same (1,1) block each step.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    partial = jnp.sum(a_ref[...].astype(acc_dtype) * b_ref[...].astype(acc_dtype))
    o_ref[...] += partial.reshape(1, 1).astype(o_ref.dtype)


# ---- pallas_call wrappers (operate on (n//128, 128) views) ---------------
def copy_2d(a2, *, block_rows: int = BLOCK_ROWS, interpret: bool = False):
    n = a2.size
    return _elementwise_call("copy", _copy_body, n, a2.dtype, 1, block_rows,
                             interpret)(a2)


def mul_2d(c2, scalar, *, block_rows: int = BLOCK_ROWS,
           interpret: bool = False):
    # `scalar` is a compile-time constant — the Mojo `alias` analogue.
    n = c2.size
    body = functools.partial(_mul_body, float(scalar))
    return _elementwise_call("mul", body, n, c2.dtype, 1, block_rows,
                             interpret)(c2)


def add_2d(a2, b2, *, block_rows: int = BLOCK_ROWS, interpret: bool = False):
    n = a2.size
    return _elementwise_call("add", _add_body, n, a2.dtype, 2, block_rows,
                             interpret)(a2, b2)


def triad_2d(b2, c2, scalar, *, block_rows: int = BLOCK_ROWS,
             interpret: bool = False):
    n = b2.size
    body = functools.partial(_triad_body, float(scalar))
    return _elementwise_call("triad", body, n, b2.dtype, 2, block_rows,
                             interpret)(b2, c2)


def dot_2d(a2, b2, *, block_rows: int = BLOCK_ROWS, interpret: bool = False):
    n = a2.size
    acc_dtype = jnp.float32 if a2.dtype in (jnp.bfloat16, jnp.float16) \
        else a2.dtype
    in_spec = pl.BlockSpec((block_rows, LANES), _tile)
    out = pl.pallas_call(
        functools.partial(_dot_body, acc_dtype=acc_dtype),
        grid=(_traced_grid("dot", n, block_rows),),
        in_specs=[in_spec, in_spec],
        # every grid step maps to the SAME (1,1) output block -> accumulator
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), a2.dtype),
        interpret=interpret,
    )(a2, b2)
    return out[0, 0]


def stream_2d_fns():
    """op name -> (2-D kernel fn, n array args, takes_scalar).

    The local-block entry points of this family: every fn consumes
    ``(rows, 128)`` views of any extent, so the sharded composite backends
    feed it per-device blocks exactly like ops.py feeds it whole arrays.
    """
    return {
        "copy": (copy_2d, 1, False),
        "mul": (mul_2d, 1, True),
        "add": (add_2d, 2, False),
        "triad": (triad_2d, 2, True),
        "dot": (dot_2d, 2, False),
    }
