"""jit'd wrappers + registry entries for the seven-point stencil."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.portable import on_tpu, register_kernel
from repro.core.metrics import stencil7_effective_bytes
from repro.kernels.stencil7 import kernel as K
from repro.kernels.stencil7 import ref


@functools.partial(jax.jit, static_argnames=(
    "invhx2", "invhy2", "invhz2", "invhxyz2", "by", "interpret"))
def laplacian_pallas(u, invhx2=1.0, invhy2=1.0, invhz2=1.0, invhxyz2=-6.0,
                     *, by=None, interpret=False):
    return K.laplacian_3d(u, invhx2, invhy2, invhz2, invhxyz2, by=by,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "invhx2", "invhy2", "invhz2", "invhxyz2"))
def laplacian_xla(u, invhx2=1.0, invhy2=1.0, invhz2=1.0, invhxyz2=-6.0):
    return ref.laplacian(u, invhx2, invhy2, invhz2, invhxyz2)


def _bytes_model(u, *args, **kw):
    # paper Eq. 1, assuming the cubic L^3 grid of the study
    L = u.shape[0]
    return stencil7_effective_bytes(L, u.dtype.itemsize)


_k = register_kernel("stencil7", bytes_model=_bytes_model,
                     doc="seven-point Laplacian stencil (paper Eq. 1 FoM)")
_k.add_backend("xla", laplacian_xla)
_k.add_backend("pallas", laplacian_pallas, available=on_tpu)
_k.add_backend("pallas_interpret",
               functools.partial(laplacian_pallas, interpret=True))
# y-tile height: the tiles must cover ny exactly and the rolling window
# (K.vmem_working_set_bytes) fit the VMEM budget — the autotuner sweeps the
# heights that do; by=None (the whole plane where it fits) is the default.
_k.declare_tunables(
    ("pallas", "pallas_interpret"),
    by=K.BY_GRID,
    constraint=lambda p, u, *a, **kw: u.shape[1] % p["by"] == 0 and
    K.vmem_working_set_bytes(u.shape, u.dtype.itemsize, p["by"])
    <= K.VMEM_BUDGET)
# AI ~= 13/24 flop/byte at fp32: memory-bound on every chip ridge the
# auditor models (cpu-host 16.7 through H100 ~295)
_k.declare_roofline_contract(("xla", "pallas", "pallas_interpret"),
                             bound="memory")
