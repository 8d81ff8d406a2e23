"""Pass 1 — dtype/promotion lint.

Two checks, both on traces of the conformance-case inputs:

  * **float64 promotion**: the kernel is re-traced under
    ``jax.enable_x64(True)`` with its (float32) case inputs.  Any
    eqn producing a float64/complex128 value with no float64 input operand
    is a latent promotion — under the default x64-disabled config jax
    silently clamps it back to f32, but the same source run with x64
    enabled (or ported to a backend without the clamp) doubles its memory
    traffic and splits from the oracle.  The classic trigger is
    ``jnp.where(mask, py_float, py_float)``: with no array operand to
    anchor the dtype, both weak scalars materialize as f64.  Integer
    widening (i32→i64 index math) is deliberately NOT flagged — it is the
    documented x64 behaviour for index arithmetic and harmless.

  * **accumulation downgrade**: on the normal trace, every ``psum`` /
    ``dot_general`` must produce at least the kernel's declared
    ``accum_dtype`` (default float32) when its inputs are floating — a
    reduction carried in bf16/f16 silently loses the oracle's precision.
"""

from __future__ import annotations

from typing import Any, List

import jax
import numpy as np

from jax.extend.core import Literal

from repro.core.analysis import jaxpr_utils as JU
from repro.core.analysis.report import Finding

#: reduction primitives audited against the declared accumulation dtype
_ACCUM_PRIMITIVES = JU.PSUM_PRIMITIVES + ("dot_general",)

_WIDE = (np.float64, np.complex128)


def _dtype_of(var: Any):
    return getattr(getattr(var, "aval", None), "dtype", None)


def run_f64_lint(kernel: str, backend: str, fn, args: tuple,
                 kwargs: dict) -> List[Finding]:
    """Re-trace under x64 and flag float64 eqns with no float64 operand."""
    with jax.enable_x64(True):
        closed = JU.trace(fn, args, kwargs)
    findings = []
    seen = set()
    for eqn in JU.iter_eqns(closed.jaxpr):
        wide_out = [v for v in eqn.outvars
                    if _dtype_of(v) is not None and _dtype_of(v) in _WIDE]
        if not wide_out:
            continue
        # a wide *traced* operand means the promotion happened upstream —
        # flag it once, there.  A wide Literal is the opposite: it IS the
        # unanchored weak scalar, so it must not anchor the eqn.
        if any(_dtype_of(v) in _WIDE for v in eqn.invars
               if not isinstance(v, Literal)):
            continue
        key = (eqn.primitive.name, tuple(str(_dtype_of(v))
                                         for v in wide_out))
        if key in seen:
            continue
        seen.add(key)
        findings.append(Finding(
            kernel=kernel, backend=backend, pass_name="dtypes",
            code="f64-promotion",
            message=(f"{eqn.primitive.name} produces "
                     f"{_dtype_of(wide_out[0])} from non-wide inputs under "
                     f"x64 — a weak Python scalar (e.g. a scalar-scalar "
                     f"jnp.where) is unanchored to the working dtype"),
            detail={"primitive": eqn.primitive.name,
                    "dtype": str(_dtype_of(wide_out[0]))}))
    return findings


def run_accum_check(kernel: str, backend: str, closed,
                    accum_dtype: str) -> List[Finding]:
    """Flag psum/dot_general eqns reducing narrower than declared."""
    declared = np.dtype(accum_dtype)
    findings = []
    seen = set()
    for eqn in JU.iter_eqns(closed.jaxpr):
        if eqn.primitive.name not in _ACCUM_PRIMITIVES:
            continue
        for v in eqn.outvars:
            dt = _dtype_of(v)
            if dt is None or not jax.numpy.issubdtype(dt, np.floating):
                continue
            if np.dtype(dt).itemsize < declared.itemsize:
                key = (eqn.primitive.name, str(dt))
                if key in seen:
                    continue
                seen.add(key)
                findings.append(Finding(
                    kernel=kernel, backend=backend, pass_name="dtypes",
                    code="accum-downgrade",
                    message=(f"{eqn.primitive.name} accumulates in {dt} "
                             f"but the kernel declares accum_dtype="
                             f"{accum_dtype}"),
                    detail={"primitive": eqn.primitive.name,
                            "dtype": str(dt),
                            "declared": accum_dtype}))
    return findings
