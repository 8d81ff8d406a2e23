"""Continuous-batching serving engine (fixed shapes, slot-granular): one
contiguous KV-cache row per slot, a prefill bucket ladder, and one serving
loop (``ServingEngine.submit``/``step``, with ``run`` a loop over them)."""

from repro.serving.engine import ServingEngine, scatter_slot_cache
from repro.serving.request import Request, RequestQueue
from repro.serving.slots import SlotAllocator
from repro.serving.trace import latency_summary, synthetic_trace

__all__ = ["ServingEngine", "scatter_slot_cache", "Request", "RequestQueue",
           "SlotAllocator", "latency_summary", "synthetic_trace"]
