"""Pass 2 — Pallas grid/BlockSpec coverage audit.

For every ``pallas_call`` in the traced backend (including calls nested in
``shard_map`` bodies), evaluate each *output* BlockSpec index map over the
whole (static) grid and prove, without running the kernel:

  * **no holes** — every block index of the output array is visited by at
    least one grid step (an unvisited block is uninitialized memory);
  * **no write races** — a block index visited by more than one grid step
    is only legal for outputs declared as sequential accumulators
    (``PortableKernel.declare_grid_contract(accumulator_outputs=...)``):
    the BabelStream dot partial and the online-softmax attention outputs
    revisit by design, everything else is the static analogue of the
    paper's atomic-update pitfalls;
  * **in-bounds tiles** — no index map may address a block outside the
    ceil(extent / block) index space (Blocked indexing clips the last
    tile, so the boundary tile itself is legal; an index *beyond* it is
    not).

The registry's declared ``TunableSpace.constraint`` is cross-checked by
the full audit: every constraint-valid tunable point is re-traced and must
still satisfy the three proofs (``repro.core.analysis.audit_cell`` drives
that sweep).
"""

from __future__ import annotations

import itertools
from typing import Any, List, Sequence, Tuple

from repro.core.analysis import jaxpr_utils as JU
from repro.core.analysis.report import Finding

#: refuse to enumerate absurd grids (no registry kernel is near this)
MAX_GRID_POINTS = 262144


def audit_grid_mapping(kernel: str, backend: str, gm: Any,
                       accumulator_outputs: Sequence[int],
                       variant: str = "") -> List[Finding]:
    """Audit one pallas_call's output coverage.  Pure index-map math."""
    findings: List[Finding] = []
    grid = tuple(int(g) for g in gm.grid)
    npoints = 1
    for g in grid:
        npoints *= g
    tag = f" [{variant}]" if variant else ""
    if npoints > MAX_GRID_POINTS:
        findings.append(Finding(
            kernel=kernel, backend=backend, pass_name="grid",
            code="grid-too-large", severity="warning",
            message=(f"grid {grid}{tag} has {npoints} points — coverage "
                     f"not enumerated (cap {MAX_GRID_POINTS})"),
            detail={"grid": list(grid)}))
        return findings

    for out_idx, bm in JU.output_block_mappings(gm):
        block = JU.blocked_dims(bm)
        if block is None:
            mode = sorted({type(d).__name__ for d in bm.block_shape})
            findings.append(Finding(
                kernel=kernel, backend=backend, pass_name="grid",
                code="unaudited-indexing-mode", severity="warning",
                message=(f"output {out_idx}{tag} uses indexing mode "
                         f"{mode}; coverage proof only models Blocked"),
                detail={"output": out_idx, "mode": mode}))
            continue
        arr_shape = tuple(int(s) for s in bm.array_aval.shape)
        nblocks = tuple(-(-s // b) for s, b in zip(arr_shape, block))

        visits: dict = {}
        for idx in JU.grid_points(grid):
            bi = JU.eval_index_map(bm.index_map_jaxpr, idx)
            visits[bi] = visits.get(bi, 0) + 1

        oob = sorted(bi for bi in visits
                     if any(i < 0 or i >= n for i, n in zip(bi, nblocks)))
        if oob:
            findings.append(Finding(
                kernel=kernel, backend=backend, pass_name="grid",
                code="out-of-bounds-tile",
                message=(f"output {out_idx}{tag}: index map addresses "
                         f"block(s) {oob[:4]} outside the "
                         f"{nblocks} block space"),
                detail={"output": out_idx, "oob": [list(b) for b in oob],
                        "nblocks": list(nblocks)}))

        holes = sorted(bi for bi in
                       itertools.product(*(range(n) for n in nblocks))
                       if bi not in visits)
        if holes:
            findings.append(Finding(
                kernel=kernel, backend=backend, pass_name="grid",
                code="coverage-hole",
                message=(f"output {out_idx}{tag}: block(s) {holes[:4]} of "
                         f"{nblocks} never written — uninitialized output"),
                detail={"output": out_idx,
                        "holes": [list(h) for h in holes[:16]],
                        "nblocks": list(nblocks)}))

        revisited = sorted(bi for bi, c in visits.items()
                           if c > 1 and bi not in set(map(tuple, oob)))
        if revisited and out_idx not in tuple(accumulator_outputs):
            findings.append(Finding(
                kernel=kernel, backend=backend, pass_name="grid",
                code="write-race",
                message=(f"output {out_idx}{tag}: block(s) "
                         f"{revisited[:4]} written by multiple grid steps "
                         f"but output {out_idx} is not a declared "
                         f"accumulator (declare_grid_contract)"),
                detail={"output": out_idx,
                        "revisited": [list(r) for r in revisited[:16]]}))
    return findings


def run(kernel: str, backend: str, closed: Any,
        accumulator_outputs: Sequence[int],
        variant: str = "") -> Tuple[List[Finding], int]:
    """Audit every pallas_call in a traced cell.  Returns (findings,
    number of pallas_calls audited) — zero calls means the pass was
    vacuous for this backend (pure-XLA), which the caller records."""
    findings: List[Finding] = []
    gms = JU.find_pallas_grid_mappings(closed.jaxpr)
    for gm in gms:
        findings.extend(audit_grid_mapping(
            kernel, backend, gm, accumulator_outputs, variant))
    return findings, len(gms)
