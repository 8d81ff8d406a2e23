"""Static analysis of lowered/compiled XLA artifacts.

`compiled.cost_analysis()` gives HLO FLOPs and bytes-accessed, but not
collective traffic.  This module parses the (compiled, post-SPMD-partitioning)
HLO text and sums the operand bytes of every collective op — the paper's
profiling role (ncu) played by the compiler IR, as fits a dry-run-only
environment.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, Optional, Tuple

__all__ = [
    "CollectiveStats",
    "parse_collective_bytes",
    "dtype_bytes",
    "parse_shape_bytes",
]

# XLA HLO collective op mnemonics we account for.
_COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1,
    "s2": 1, "s4": 1, "s8": 1, "s16": 2, "s32": 4, "s64": 8,
    "u2": 1, "u4": 1, "u8": 1, "u16": 2, "u32": 4, "u64": 8,
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}


def dtype_bytes(dtype: str) -> int:
    try:
        return _DTYPE_BYTES[dtype]
    except KeyError as e:
        raise ValueError(f"unknown HLO dtype {dtype!r}") from e


# An HLO shape like  bf16[256,4096]{1,0}  or  f32[] — capture dtype + dims.
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:[a-z0-9]*)?)\[([0-9,]*)\]")

# Start of an HLO instruction line:  %name = <shape-or-tuple> opcode(
# We match the result type region then look for the collective opcode.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?[%\w.\-]+\s*=\s*(\([^)]*\)|[^\s(]+)\s+"
    r"(" + "|".join(_COLLECTIVE_KINDS) + r")(?:-start|-done)?\b",
)


def parse_shape_bytes(shape_text: str) -> int:
    """Sum bytes over all array shapes appearing in `shape_text`."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue  # e.g. token[] / opaque
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    """Per-kind collective byte/opcount totals for one HLO module."""

    bytes_by_kind: Dict[str, int]
    count_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())

    def summary(self) -> Dict[str, Dict[str, int]]:
        return {
            k: {"count": self.count_by_kind.get(k, 0),
                "bytes": self.bytes_by_kind.get(k, 0)}
            for k in sorted(set(self.bytes_by_kind) | set(self.count_by_kind))
        }


def parse_collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective instruction.

    We use the *result* shape of each collective (the tuple/array on the LHS):
    for all-gather that is the gathered (larger) output, for reduce-scatter
    the scattered (smaller) output, for all-reduce the full buffer — a
    reasonable, conservative proxy for link traffic per op.  `-start/-done`
    async pairs are counted once (on `-start`; bare ops counted normally).
    """
    bytes_by_kind: Dict[str, int] = defaultdict(int)
    count_by_kind: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        if "-done" in line.split("=", 1)[1].split("(", 1)[0]:
            # async completion: payload already counted at -start
            continue
        result_region, kind = m.group(1), m.group(2)
        nbytes = parse_shape_bytes(result_region)
        bytes_by_kind[kind] += nbytes
        count_by_kind[kind] += 1
    return CollectiveStats(bytes_by_kind=dict(bytes_by_kind),
                           count_by_kind=dict(count_by_kind))
