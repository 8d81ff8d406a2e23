"""PortableKernel — the paper's contribution as a composable JAX abstraction.

The Mojo paper's thesis: write a kernel ONCE against a portable, compile-time
specialized abstraction, lower it to multiple targets, and measure efficiency
against each target's "vendor" baseline.  Here:

  * a *kernel spec* is a named operation with a figure-of-merit model
    (FLOPs / moved bytes as a function of the input shapes — paper Eqs. 1-3);
  * *backends* are alternative implementations of the same spec:
      - ``xla``              pure-jnp oracle, what XLA autotunes (the "vendor"
                             baseline analogue of CUDA/HIP),
      - ``pallas``           the Pallas-TPU kernel (MLIR compile-time
                             specialized, the "Mojo" analogue),
      - ``pallas_interpret`` the same Pallas kernel body interpreted on CPU
                             (correctness validation path used by CI);
  * backends declare *availability* (pallas-TPU only runs on TPU) and are
    skipped — never crashed into — when unavailable
    (``BackendUnavailableError`` carries the reason);
  * backends declare a *tunable space* (block/tile sizes); the autotuner in
    ``repro.core.tuning`` sweeps it deterministically and persists the best
    point per (kernel, backend, shape, dtype, platform), so Eq.-4 efficiency
    is always measured at each backend's best configuration — untuned
    portable kernels understate the metric (Godoy et al., 2023);
  * the registry can *validate* any backend against the oracle and *time* all
    backends to feed the performance-portability metric (paper Eq. 4).

Framework layers (attention, RWKV, MoE dispatch, science kernels) register
here so deployments choose backends by name and CI sweeps them uniformly;
``benchmarks/portability.py`` walks this registry to produce the tuned Eq.-4
table.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import (Any, Callable, Dict, Iterator, List, Mapping, Optional,
                    Sequence, Tuple, Union)

import jax
import numpy as np

from repro.core import telemetry as tel

__all__ = [
    "Backend",
    "BackendUnavailableError",
    "TunableSpace",
    "PortableKernel",
    "KernelRegistry",
    "registry",
    "register_kernel",
    "get_kernel",
    "on_tpu",
]


class BackendUnavailableError(RuntimeError):
    """A backend exists in the registry but cannot run on this host."""


@dataclasses.dataclass(frozen=True)
class Backend:
    """One implementation of a kernel spec."""

    name: str
    fn: Callable[..., Any]
    # True when this backend is expected to run on the *current* process
    # (pallas-TPU kernels only run on TPU; interpret/xla run anywhere).
    available: Callable[[], bool] = lambda: True
    # why the last is_available() said False — conformance skips and the
    # static-auditor report surface it instead of a bare False
    unavailable_reason: Optional[str] = dataclasses.field(
        default=None, compare=False)

    def is_available(self) -> bool:
        try:
            ok = bool(self.available())
        except Exception as exc:
            object.__setattr__(
                self, "unavailable_reason",
                f"availability probe raised {type(exc).__name__}: {exc}")
            return False
        reason = None
        if not ok:
            pred = getattr(self.available, "__qualname__",
                           repr(self.available))
            reason = f"availability predicate {pred} returned False"
        object.__setattr__(self, "unavailable_reason", reason)
        return ok

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        return self.fn(*args, **kwargs)


@dataclasses.dataclass(frozen=True)
class TunableSpace:
    """Declared tunable parameters of one backend.

    ``params`` maps parameter name -> candidate values (declaration order is
    the deterministic sweep order).  ``constraint(point, *args, **kwargs)``
    filters points that are invalid for the concrete inputs (e.g. a block
    size that does not divide the array extent).
    """

    params: Mapping[str, Tuple[Any, ...]]
    constraint: Optional[Callable[..., bool]] = None

    def points(self) -> Iterator[Dict[str, Any]]:
        """Deterministic cartesian product over the declared grid."""
        names = list(self.params)
        for values in itertools.product(*(self.params[n] for n in names)):
            yield dict(zip(names, values))

    def valid_points(self, *args: Any, **kwargs: Any) -> List[Dict[str, Any]]:
        pts = []
        for p in self.points():
            if self.constraint is None or self.constraint(p, *args, **kwargs):
                pts.append(p)
        return pts


@dataclasses.dataclass
class PortableKernel:
    """A named kernel spec with multiple backends and a figure-of-merit model.

    ``flops_model`` / ``bytes_model`` take the same (abstract) arguments as
    the kernel and return the paper-defined operation/byte counts used for
    the GFLOP/s and effective-bandwidth figures of merit.
    """

    name: str
    backends: Dict[str, Backend] = dataclasses.field(default_factory=dict)
    oracle: str = "xla"
    flops_model: Optional[Callable[..., float]] = None
    bytes_model: Optional[Callable[..., float]] = None
    doc: str = ""
    tunables: Dict[str, TunableSpace] = dataclasses.field(default_factory=dict)
    #: dtype every reduction in this kernel must accumulate in (or wider);
    #: the static auditor flags psum/dot_general eqns reducing narrower
    accum_dtype: str = "float32"
    #: backend name -> declared communication contract (see
    #: ``declare_comm_contract``); audited against the traced jaxpr
    comm_contracts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: backend name -> grid-coverage metadata (see ``declare_grid_contract``)
    grid_contracts: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    #: backend name -> static performance expectations (see
    #: ``declare_roofline_contract``); audited by ``analysis.cost``
    roofline_contracts: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)

    # ---- registration -------------------------------------------------
    def add_backend(self, name: str, fn: Callable[..., Any],
                    available: Callable[[], bool] = lambda: True) -> None:
        self.backends[name] = Backend(name=name, fn=fn, available=available)

    def declare_tunables(
            self, backends: Union[str, Sequence[str]], *,
            constraint: Optional[Callable[..., bool]] = None,
            **params: Sequence[Any]) -> None:
        """Declare the tunable grid for one or more backends.

        ``declare_tunables(("pallas", "pallas_interpret"), by=(8, 16, 32))``
        registers the same space under both names (the interpret backend is
        the same kernel body, so it shares the space).
        """
        space = TunableSpace(
            params={k: tuple(v) for k, v in params.items()},
            constraint=constraint)
        names = [backends] if isinstance(backends, str) else list(backends)
        for n in names:
            self.tunables[n] = space

    def tunable_space(self, backend: str) -> Optional[TunableSpace]:
        return self.tunables.get(backend)

    def declare_comm_contract(self, backends: Union[str, Sequence[str]],
                              contract: Any) -> None:
        """Declare the collective traffic one (sharded) backend may emit.

        ``contract`` is either a dict
        ``{"ppermute": n, "psum": n, "all_gather": n}`` (one traced variant,
        default call parameters), or a callable ``contract(*case_args)``
        returning a list of ``(variant_kwargs, expectation_dict)`` pairs —
        the auditor traces the backend once per variant.  An expectation may
        also carry ``"overlap_shape": tuple``: the variant must contain an
        interior compute of that shape with no data dependency on any
        ``ppermute`` output (the halo/compute-overlap contract).  Backends
        with no declared contract are audited against *zero* collectives.
        """
        names = [backends] if isinstance(backends, str) else list(backends)
        for n in names:
            self.comm_contracts[n] = contract

    def comm_contract(self, backend: str) -> Any:
        return self.comm_contracts.get(backend)

    def declare_grid_contract(self, backends: Union[str, Sequence[str]], *,
                              accumulator_outputs: Sequence[int] = ()) -> None:
        """Declare Pallas grid-coverage metadata for one or more backends.

        ``accumulator_outputs`` lists output indices whose block is *meant*
        to be revisited across grid steps (sequential accumulators like the
        BabelStream dot partial or flash attention's online-softmax output).
        Any other revisited output block is a write race; unvisited blocks
        are holes — both are auditor findings.
        """
        names = [backends] if isinstance(backends, str) else list(backends)
        for n in names:
            self.grid_contracts[n] = {
                "accumulator_outputs": tuple(accumulator_outputs)}

    def grid_contract(self, backend: str) -> Dict[str, Any]:
        return self.grid_contracts.get(backend, {})

    def declare_roofline_contract(
            self, backends: Union[str, Sequence[str]], *,
            bound: Optional[str] = None,
            traffic_inflation_limit: Optional[float] = None) -> None:
        """Pin the static performance auditor's expectations for a backend.

        ``bound`` is the expected roofline verdict at the conformance-case
        shape ("memory" | "compute" | "collective") — declare it only where
        the verdict is platform-robust; the auditor flags a flip as a
        regression.  ``traffic_inflation_limit`` overrides the default
        modeled-traffic-over-compulsory-bytes limit for kernels whose halo
        re-reads or accumulator revisits are by design.
        """
        if bound is not None and bound not in ("memory", "compute",
                                               "collective"):
            raise ValueError(f"unknown roofline bound {bound!r}")
        contract: Dict[str, Any] = {}
        if bound is not None:
            contract["bound"] = bound
        if traffic_inflation_limit is not None:
            contract["traffic_inflation_limit"] = \
                float(traffic_inflation_limit)
        names = [backends] if isinstance(backends, str) else list(backends)
        for n in names:
            self.roofline_contracts[n] = contract

    def roofline_contract(self, backend: str) -> Dict[str, Any]:
        return self.roofline_contracts.get(backend, {})

    def backend(self, name: Optional[str] = None) -> Backend:
        if name is None:
            name = self.default_backend()
        if name not in self.backends:
            raise KeyError(
                f"kernel {self.name!r} has no backend {name!r}; "
                f"have {sorted(self.backends)}")
        return self.backends[name]

    def available_backends(self) -> List[str]:
        return [n for n in sorted(self.backends)
                if self.backends[n].is_available()]

    def default_backend(self) -> str:
        """Pallas on TPU, oracle elsewhere — the paper's portability story.

        Honors ``Backend.available``: an unavailable pallas backend falls
        back to the oracle, an unavailable oracle falls back to any
        available backend, and only when *nothing* can run do we raise
        ``BackendUnavailableError`` (never a crash inside the backend).
        """
        pallas = self.backends.get("pallas")
        if pallas is not None and _on_tpu() and pallas.is_available():
            return "pallas"
        oracle = self.backends.get(self.oracle)
        if oracle is None:
            # spec-only kernel (no backends registered yet): keep returning
            # the declared oracle name so callers get the usual KeyError.
            return self.oracle
        if oracle.is_available():
            return self.oracle
        for n in self.available_backends():
            return n
        raise BackendUnavailableError(
            f"kernel {self.name!r}: no backend available on this host "
            f"(registered: {sorted(self.backends)})")

    def _require_available(self, name: str) -> Backend:
        b = self.backend(name)
        if not b.is_available():
            raise BackendUnavailableError(
                f"kernel {self.name!r} backend {name!r} is not available on "
                f"this host: {b.unavailable_reason} "
                f"(available: {self.available_backends()})")
        return b

    def __call__(self, *args: Any, backend: Optional[str] = None,
                 tuned: bool = False, tuning_cache: Any = None,
                 **kwargs: Any) -> Any:
        """Run the kernel.

        With ``tuned=True`` the persistent tuning cache (see
        ``repro.core.tuning``) is consulted for the best block/tile sizes
        recorded for this (kernel, backend, shape, dtype, platform); cached
        parameters are merged *under* explicit kwargs, and a cache miss
        silently runs the declared defaults.
        """
        name = backend if backend is not None else self.default_backend()
        if tuned:
            from repro.core import tuning as _tuning
            best = _tuning.cached_best_params(
                self, *args, backend=name, cache=tuning_cache, **kwargs)
            kwargs = {**best, **kwargs}
        return self.backend(name)(*args, **kwargs)

    # ---- validation ----------------------------------------------------
    def validate(self, *args: Any, backend: str,
                 rtol: Optional[float] = None, atol: Optional[float] = None,
                 **kwargs: Any) -> None:
        """assert_allclose the given backend against the oracle.

        Default tolerances come from the conformance tables
        (``repro.core.conformance.oracle_tolerance``), so ad-hoc validation
        and the conformance matrix cannot disagree: a ``"bitwise"`` cell
        validates at rtol=atol=0, an unregistered kernel falls back to
        (1e-5, 1e-5).  Explicit ``rtol``/``atol`` override per call.

        Raises ``BackendUnavailableError`` (not an opaque crash from inside
        the kernel) when either side cannot run here.
        """
        if rtol is None or atol is None:
            from repro.core import conformance
            tol = conformance.oracle_tolerance(self.name, backend)
            d_rtol, d_atol = ((0.0, 0.0) if tol == "bitwise"
                              else tol if tol is not None else (1e-5, 1e-5))
            rtol = d_rtol if rtol is None else rtol
            atol = d_atol if atol is None else atol
        want = self._require_available(self.oracle)(*args, **kwargs)
        got = self._require_available(backend)(*args, **kwargs)
        jax.tree.map(
            lambda w, g: np.testing.assert_allclose(
                np.asarray(g, dtype=np.float64),
                np.asarray(w, dtype=np.float64), rtol=rtol, atol=atol),
            want, got)

    # ---- measurement ---------------------------------------------------
    def time_backend(self, *args: Any, backend: str, iters: int = 10,
                     warmup: int = 2, **kwargs: Any) -> float:
        """Median wall-clock seconds per call (post-warmup, paper §3).

        The paper discards the first (JIT) step and reports medians over many
        runs; we do the same.  ``warmup=0`` is allowed (the timed loop then
        includes compilation in its first sample — the median still drops it
        for ``iters >= 3``).

        Each call emits one ``registry.time_backend`` telemetry span tagged
        with (kernel, backend, params) — the per-measurement provenance the
        Eq.-4 table is built from — with per-iteration ``registry.measure``
        child spans inside it, plus one ``registry.time_backend.result``
        instant carrying the shape signature and median seconds (the join
        key the static auditor's drift gate re-traces predictions from).
        All events fire at the driver level, outside the measured regions'
        compiled code, and timing uses the same ``perf_counter`` reads as
        before: telemetry off is bitwise the status quo.
        """
        fn = self._require_available(backend)
        params = {k: v for k, v in kwargs.items()
                  if isinstance(v, (bool, int, float, str, tuple))}
        with tel.span("registry.time_backend", proc="registry",
                      kernel=self.name, backend=backend, iters=iters,
                      warmup=warmup, params=params):
            out = None
            for _ in range(warmup):
                out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            times = []
            for _ in range(iters):
                with tel.span("registry.measure", proc="registry",
                              kernel=self.name, backend=backend):
                    t0 = time.perf_counter()
                    out = fn(*args, **kwargs)
                    jax.block_until_ready(out)
                    times.append(time.perf_counter() - t0)
        tel.counter("registry.time_backend.calls", proc="registry")
        median_s = float(np.median(times))
        if tel.enabled():
            import json as _json

            from repro.core import tuning as _tuning
            base = {k: v for k, v in kwargs.items() if k not in params}
            tel.instant(
                "registry.time_backend.result", proc="registry",
                kernel=self.name, backend=backend,
                shape=_tuning.shape_signature(*args, **base),
                params_json=_json.dumps(params, sort_keys=True, default=repr),
                seconds=median_s, iters=iters,
                devices=jax.device_count(),
                platform=jax.devices()[0].platform)
        return median_s

    def figure_of_merit(self, elapsed_s: float, *args: Any,
                        **kwargs: Any) -> Dict[str, float]:
        """GFLOP/s and GB/s from the paper's operation/byte models."""
        out: Dict[str, float] = {"seconds": elapsed_s}
        if self.flops_model is not None:
            out["gflops_per_s"] = self.flops_model(*args, **kwargs) / elapsed_s / 1e9
        if self.bytes_model is not None:
            out["gbytes_per_s"] = self.bytes_model(*args, **kwargs) / elapsed_s / 1e9
        return out


def _on_tpu() -> bool:
    # a backend that fails to initialize raises here: it is not "not a TPU"
    return jax.devices()[0].platform == "tpu"


#: availability predicate for compiled pallas-TPU backends (public so
#: kernel ops modules can pass ``available=on_tpu`` at registration).
on_tpu = _on_tpu


class KernelRegistry:
    """Global name → PortableKernel map (the framework's kernel catalogue)."""

    def __init__(self) -> None:
        self._kernels: Dict[str, PortableKernel] = {}

    def register(self, kernel: PortableKernel) -> PortableKernel:
        if kernel.name in self._kernels:
            raise ValueError(f"duplicate kernel {kernel.name!r}")
        self._kernels[kernel.name] = kernel
        return kernel

    def get(self, name: str) -> PortableKernel:
        try:
            return self._kernels[name]
        except KeyError:
            raise KeyError(
                f"no kernel {name!r} registered; "
                f"registered kernels: {self.names()}") from None

    def __contains__(self, name: str) -> bool:
        return name in self._kernels

    def names(self) -> Sequence[str]:
        return sorted(self._kernels)


registry = KernelRegistry()


def register_kernel(name: str, *, oracle: str = "xla",
                    flops_model: Optional[Callable[..., float]] = None,
                    bytes_model: Optional[Callable[..., float]] = None,
                    doc: str = "") -> PortableKernel:
    """Create-or-get a PortableKernel in the global registry."""
    if name in registry:
        return registry.get(name)
    return registry.register(PortableKernel(
        name=name, oracle=oracle, flops_model=flops_model,
        bytes_model=bytes_model, doc=doc))


def get_kernel(name: str) -> PortableKernel:
    return registry.get(name)
