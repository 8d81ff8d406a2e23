"""Three-term roofline model from compiled dry-run artifacts.

    compute term    = HLO_FLOPs       / (chips x peak_FLOP/s)
    memory term     = HLO_bytes       / (chips x HBM_bw)
    collective term = collective_bytes/ (chips x link_bw)

Hardware constants are the assignment's TPU v5e-class chip.  cost_analysis()
reports *per-partition* (single-program) numbers under SPMD, i.e. already
per-chip; we therefore do NOT divide FLOPs/bytes by the chip count again —
`chips` enters only through the per-chip peak rates.  Collective bytes parsed
from the SPMD module are likewise per-chip payloads.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Mapping, Optional

from repro.core.hlo_analysis import CollectiveStats, parse_collective_bytes
from repro.core.hlo_cost import HloCost, analyze_hlo

__all__ = ["ChipSpec", "TPU_V5E", "NVIDIA_H100", "AMD_MI300A", "CPU_HOST",
           "CHIP_SPECS", "TPU_SPECS", "detect_chip", "RooflineTerms",
           "roofline_from_compiled", "model_flops"]


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops: float        # FLOP/s (bf16)
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per link
    hbm_bytes: float         # capacity

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (FLOP/byte) at the compute/memory knee."""
        return self.peak_flops / self.hbm_bw


TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops=197e12,      # 197 TFLOP/s bf16
    hbm_bw=819e9,           # 819 GB/s
    ici_bw=50e9,            # ~50 GB/s/link
    hbm_bytes=16 * 2 ** 30,
)

# The paper's two GPU targets (Table: H100 PCIe/SXM and MI300A APU).
NVIDIA_H100 = ChipSpec(
    name="nvidia-h100",
    peak_flops=989e12,      # 989 TFLOP/s bf16 dense (SXM)
    hbm_bw=3.35e12,         # HBM3
    ici_bw=450e9,           # NVLink per direction
    hbm_bytes=80 * 2 ** 30,
)

AMD_MI300A = ChipSpec(
    name="amd-mi300a",
    peak_flops=981e12,      # 980.6 TFLOP/s bf16
    hbm_bw=5.3e12,          # unified HBM3
    ici_bw=128e9,           # Infinity Fabric link
    hbm_bytes=128 * 2 ** 30,
)

# Calibration floor for hosts without an accelerator (CI, laptops): a
# vectorized server core-complex.  Verdicts on this spec are only used
# relatively (the drift gate self-calibrates); the ridge (~16 FLOP/byte)
# is deliberately in the same decade as the real chips so bound verdicts
# transfer.
CPU_HOST = ChipSpec(
    name="cpu-host",
    peak_flops=5e11,
    hbm_bw=3e10,
    ici_bw=1e10,
    hbm_bytes=16 * 2 ** 30,
)

CHIP_SPECS: Dict[str, ChipSpec] = {
    c.name: c for c in (TPU_V5E, NVIDIA_H100, AMD_MI300A, CPU_HOST)
}

#: TPU peaks keyed by the ``device_kind`` JAX reports; a TPU kind missing
#: here is an error, never another chip's peaks
TPU_SPECS: Dict[str, ChipSpec] = {"TPU v5 lite": TPU_V5E}


def detect_chip(platform: Optional[str] = None,
                device_kind: Optional[str] = None) -> ChipSpec:
    """Map the local jax backend (or explicit platform/device_kind strings)
    to the ChipSpec whose peaks the roofline verdict should name.

    TPUs are looked up by ``device_kind`` in ``TPU_SPECS`` (an unknown kind
    raises ``ValueError``), GPU hosts are split H100 vs MI300A on the
    device-kind string, and everything else (the CPU CI lane, forced host
    devices) maps to ``CPU_HOST``.  A backend that fails to initialize
    raises; it is not read as a CPU.
    """
    if platform is None:
        import jax
        dev = jax.devices()[0]
        platform, device_kind = dev.platform, dev.device_kind
    platform = platform.lower()
    if platform == "tpu":
        if device_kind not in TPU_SPECS:
            raise ValueError(
                f"no peak table for TPU device_kind {device_kind!r}; "
                f"known kinds: {sorted(TPU_SPECS)}")
        return TPU_SPECS[device_kind]
    kind = (device_kind or "").lower()
    if platform in ("gpu", "cuda", "rocm"):
        if "mi300" in kind or "amd" in kind or platform == "rocm":
            return AMD_MI300A
        return NVIDIA_H100
    return CPU_HOST


@dataclasses.dataclass
class RooflineTerms:
    """Per-step roofline terms, in seconds, for one (arch, shape, mesh)."""

    flops: float                  # per-chip HLO FLOPs
    hbm_bytes: float              # per-chip HLO bytes accessed
    collective_bytes: float       # per-chip collective payload bytes
    compute_s: float
    memory_s: float
    collective_s: float
    collectives: Dict[str, Dict[str, int]]
    # memory_analysis numbers (per chip)
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    peak_bytes: int = 0
    # raw cost_analysis (loop bodies counted once — lower bounds)
    xla_flops: float = 0.0
    xla_bytes: float = 0.0
    unknown_trip_loops: int = 0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Roofline lower bound on step time (max of the three terms)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self) | {
            "dominant": self.dominant, "bound_s": self.bound_s}


def roofline_from_compiled(compiled: Any, chip: ChipSpec = TPU_V5E,
                           hlo_text: Optional[str] = None,
                           kernel_adjusted: bool = False) -> RooflineTerms:
    """Derive RooflineTerms from a jax `Compiled` object.

    Primary source is the trip-count-aware HLO walk (core/hlo_cost.py):
    XLA's own cost_analysis() counts while-loop bodies once, which under
    scan-over-layers + microbatching understates FLOPs by orders of
    magnitude.  Raw cost_analysis numbers are retained in `xla_*` fields
    for cross-checking (they form a lower bound).

    kernel_adjusted=True costs the named-scope tiles that the validated
    Pallas kernels (flash attention, WKV) keep VMEM-resident at zero HBM —
    the deployed-kernel roofline vs the plain-XLA roofline.
    """
    from repro.core.hlo_cost import KERNEL_VMEM_SCOPES
    text = hlo_text if hlo_text is not None else compiled.as_text()
    hc = analyze_hlo(text, vmem_scopes=KERNEL_VMEM_SCOPES
                     if kernel_adjusted else ())

    cost = compiled.cost_analysis()
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))

    mem = compiled.memory_analysis()
    arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
    out_b = int(getattr(mem, "output_size_in_bytes", 0))
    tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
    alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
    # peak live = args + outputs + temps - aliased (donated args reused)
    peak_b = arg_b + out_b + tmp_b - alias_b

    flops = max(hc.flops, xla_flops)
    hbm_bytes = hc.hbm_bytes if kernel_adjusted \
        else max(hc.hbm_bytes, xla_bytes)
    coll_bytes = hc.collective_bytes
    colls = {k: {"count": int(hc.collective_count_by_kind.get(k, 0)),
                 "bytes": int(v)}
             for k, v in sorted(hc.collective_bytes_by_kind.items())}

    return RooflineTerms(
        flops=flops,
        hbm_bytes=hbm_bytes,
        collective_bytes=coll_bytes,
        compute_s=flops / chip.peak_flops,
        memory_s=hbm_bytes / chip.hbm_bw,
        collective_s=coll_bytes / chip.ici_bw,
        collectives=colls,
        argument_bytes=arg_b,
        output_bytes=out_b,
        temp_bytes=tmp_b,
        peak_bytes=peak_b,
        xla_flops=xla_flops,
        xla_bytes=xla_bytes,
        unknown_trip_loops=hc.unknown_trip_loops,
    )


def model_flops(n_params_active: float, tokens: float,
                kind: str = "train") -> float:
    """MODEL_FLOPS = 6·N·D for training; 2·N·D for a forward/decode pass.

    For MoE, pass the *active* parameter count.
    """
    per_token = 6.0 if kind == "train" else 2.0
    return per_token * n_params_active * tokens
