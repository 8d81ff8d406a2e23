"""Slot allocator for the continuous-batching engine.

A slot is one *batch row* of the engine's decode program and one row of
its KV cache.  Requests borrow a slot for their whole lifetime (prefill
through last decode step) and return it on completion; the allocator is a
plain free heap — lowest id first, so rows are reused densely.  A set
shadows the heap so double-free detection is O(1) instead of an O(n) heap
scan.
"""

from __future__ import annotations

import heapq
from typing import List, Set


class SlotAllocator:
    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise ValueError("need at least one slot")
        self.num_slots = num_slots
        self._free: List[int] = list(range(num_slots))
        heapq.heapify(self._free)
        self._free_set: Set[int] = set(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("no free slots")
        slot = heapq.heappop(self._free)
        self._free_set.discard(slot)
        return slot

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if slot in self._free_set:          # O(1), not an O(n) heap scan
            raise ValueError(f"slot {slot} already free")
        heapq.heappush(self._free, slot)
        self._free_set.add(slot)

    def available(self) -> int:
        return len(self._free)

    def in_use(self) -> int:
        return self.num_slots - len(self._free)
