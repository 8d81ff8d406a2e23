"""Shared jaxpr machinery for the static auditor.

Everything here works on *traced* programs only — ``jax.make_jaxpr``
abstract-evaluates the backend on the conformance-case inputs, so compiled
``pallas`` backends trace off-TPU and ``shard_map`` bodies trace on any
host with enough (possibly forced) devices, all without executing a single
kernel.  The recursive walk descends into every eqn param that holds a
sub-jaxpr (``pjit``, ``scan``, ``while``, ``shard_map``, ``pallas_call``,
``custom_*`` — anything carrying a ``Jaxpr``/``ClosedJaxpr`` or a
list/tuple of them), so a collective or a float64 eqn cannot hide inside a
nested trace.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

#: ``jax.shard_map`` spells psum ``psum_invariant`` under ``check_vma=True``;
#: both count as psum.
PSUM_PRIMITIVES = ("psum", "psum_invariant")
COLLECTIVE_KINDS = ("ppermute", "psum", "all_gather")


def _iter_subjaxprs(params: Dict[str, Any]) -> Iterator[Jaxpr]:
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for s in vals:
            inner = getattr(s, "jaxpr", s)
            if isinstance(inner, Jaxpr):
                yield inner


def iter_eqns(jaxpr: Jaxpr) -> Iterator[Any]:
    """Every eqn of ``jaxpr`` and (recursively) of every nested sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _iter_subjaxprs(eqn.params):
            yield from iter_eqns(inner)


def trace(fn: Callable[..., Any], args: tuple, kwargs: dict) -> ClosedJaxpr:
    """Closed jaxpr of ``fn(*args, **kwargs)`` — abstract eval, no run."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)


def count_collectives(jaxpr: Jaxpr) -> Dict[str, int]:
    """Collective-primitive census: ppermute / psum / all_gather."""
    counts = {k: 0 for k in COLLECTIVE_KINDS}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in PSUM_PRIMITIVES:
            counts["psum"] += 1
        elif name in ("ppermute", "all_gather"):
            counts[name] += 1
    return counts


def find_pallas_grid_mappings(jaxpr: Jaxpr) -> List[Any]:
    """``grid_mapping`` of every ``pallas_call`` eqn, however nested."""
    return [eqn.params["grid_mapping"] for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def find_shard_map_bodies(jaxpr: Jaxpr) -> List[Jaxpr]:
    """Body jaxprs of every ``shard_map`` eqn, however nested."""
    bodies = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "shard_map":
            body = eqn.params["jaxpr"]
            bodies.append(getattr(body, "jaxpr", body))
    return bodies


def independent_compute_exists(body: Jaxpr, shape: Tuple[int, ...]) -> bool:
    """True when ``body`` contains an eqn output of ``shape`` that depends
    on a body input but on NO ``ppermute`` output — the static witness of
    halo/compute overlap (the interior stencil must be schedulable while
    the halo traffic is in flight).  Non-overlapped bodies compute only on
    the halo-padded block, so every full-shape eqn is ppermute-tainted."""
    tainted: set = set()
    from_input = {str(v) for v in body.invars}
    found = False
    for eqn in body.eqns:
        ins = [str(v) for v in eqn.invars if not isinstance(v, Literal)]
        is_tainted = (eqn.primitive.name == "ppermute"
                      or any(v in tainted for v in ins))
        depends = any(v in from_input for v in ins)
        for v in eqn.outvars:
            if is_tainted:
                tainted.add(str(v))
            if depends:
                from_input.add(str(v))
        if (not is_tainted and depends
                and any(tuple(getattr(v.aval, "shape", ())) == tuple(shape)
                        for v in eqn.outvars)):
            found = True
    return found


def eval_index_map(index_map_jaxpr: ClosedJaxpr,
                   idx: Tuple[int, ...]) -> Tuple[int, ...]:
    """Evaluate one BlockSpec index map at a concrete grid point."""
    out = jax.core.eval_jaxpr(index_map_jaxpr.jaxpr, index_map_jaxpr.consts,
                              *idx)
    return tuple(int(v) for v in out)


def blocked_dims(bm: Any) -> Optional[Tuple[int, ...]]:
    """Block extents of a BlockMapping whose every dim is ``Blocked`` or
    ``Squeezed`` (extent 1); ``None`` when a dim uses element or
    bounded-slice indexing, which the coverage and traffic models do not
    cover."""
    dims = []
    for d in bm.block_shape:
        kind = type(d).__name__
        if kind == "Blocked":
            dims.append(int(d.block_size))
        elif kind == "Squeezed":
            dims.append(1)
        else:
            return None
    return tuple(dims)


def in_hbm(aval: Any) -> bool:
    """Whether a Pallas ref or block lives in HBM (``pl.ANY`` or HBM): such
    an operand is not pipelined block by block but moved by the kernel's
    own DMAs."""
    return str(getattr(aval, "memory_space", "")).lower() in ("any", "hbm")


def dma_copy(eqn: Any) -> Tuple[int, bool, bool]:
    """(bytes, source in HBM, destination in HBM) of a ``dma_start`` eqn,
    from the shapes of the sliced source and destination refs."""
    src, _, dst, dst_tf = jax.tree_util.tree_unflatten(
        eqn.params["tree"], [v.aval for v in eqn.invars])[:4]
    shape = dst_tf[-1].get_indexer_shape() if dst_tf else dst.shape
    nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dst.dtype).itemsize
    return nbytes, in_hbm(src), in_hbm(dst)


def output_block_mappings(grid_mapping: Any) -> List[Tuple[int, Any]]:
    """(output_index, BlockMapping) for each pallas output, identified by
    the mapping's ``origin`` with a positional fallback (inputs precede
    outputs in ``block_mappings``; scalar-prefetch operands have none)."""
    mappings = list(grid_mapping.block_mappings)
    outs = [bm for bm in mappings
            if bm is not None and "output" in str(getattr(bm, "origin", ""))]
    if not outs:
        n_out = grid_mapping.num_outputs
        outs = [m for m in mappings[-n_out:] if m is not None]
    return list(enumerate(outs))


def grid_points(grid: Tuple[int, ...]) -> Iterator[Tuple[int, ...]]:
    """Row-major walk of the (static) grid index space."""
    import itertools
    yield from itertools.product(*(range(int(g)) for g in grid))
