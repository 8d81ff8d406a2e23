"""Pure-jnp oracle for the seven-point stencil (paper Listing 2 semantics).

f[i,j,k] = u[i,j,k]*invhxyz2 + (u[i,j,k-1]+u[i,j,k+1])*invhx2
                             + (u[i,j-1,k]+u[i,j+1,k])*invhy2
                             + (u[i-1,j,k]+u[i+1,j,k])*invhz2
on interior cells; boundary cells are zero (the HIP baseline never writes
them; we fix them to 0 so both implementations are pure functions).
Axis order is (z, y, x), x contiguous.
"""

from __future__ import annotations

import jax.numpy as jnp


def default_coefficients(hx: float = 1.0, hy: float = 1.0, hz: float = 1.0):
    invhx2, invhy2, invhz2 = 1.0 / hx ** 2, 1.0 / hy ** 2, 1.0 / hz ** 2
    invhxyz2 = -2.0 * (invhx2 + invhy2 + invhz2)
    return invhx2, invhy2, invhz2, invhxyz2


def laplacian(u: jnp.ndarray, invhx2: float, invhy2: float, invhz2: float,
              invhxyz2: float) -> jnp.ndarray:
    c = u.dtype.type
    if min(u.shape) < 2:   # one cell thick: all boundary, and the pad below
        return jnp.zeros_like(u)  # would make that axis two cells long
    core = (u[1:-1, 1:-1, 1:-1] * c(invhxyz2)
            + (u[1:-1, 1:-1, :-2] + u[1:-1, 1:-1, 2:]) * c(invhx2)
            + (u[1:-1, :-2, 1:-1] + u[1:-1, 2:, 1:-1]) * c(invhy2)
            + (u[:-2, 1:-1, 1:-1] + u[2:, 1:-1, 1:-1]) * c(invhz2))
    return jnp.pad(core, 1)
