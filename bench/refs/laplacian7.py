"""Plain reference of the seven-point Laplacian (paper Listing 2).

    f[z,y,x] = u[z,y,x]*c + (u[z,y,x-1] + u[z,y,x+1])*ax
             + (u[z,y-1,x] + u[z,y+1,x])*ay + (u[z-1,y,x] + u[z+1,y,x])*az

on interior cells, 0 on the boundary.  The reference is computed in
float64 with NumPy on the host, block by block of z-planes; it shares no
code with the program.
"""

from typing import Dict

import numpy as np

#: z-planes per block of the host reference
BLOCK = 64


def make_inputs(cfg: Dict, key):
    """The stencil's input volume, made on the device from ``key``."""
    import jax
    import jax.numpy as jnp
    shape, dtype = tuple(cfg["shape"]), jnp.dtype(cfg["dtype"])
    return (jax.jit(lambda k: jax.random.normal(k, shape, dtype))(key),)


def coefficients(cfg: Dict) -> Dict[str, float]:
    return {k: float(v) for k, v in cfg["coefficients"].items()}


def _block(u: np.ndarray, z0: int, z1: int, c: Dict[str, float]):
    """Reference planes [z0, z1) in float64 from the float64 volume ``u``."""
    nz, ny, nx = u.shape
    out = np.zeros((z1 - z0, ny, nx), np.float64)
    lo, hi = max(z0, 1), min(z1, nz - 1)
    if hi > lo:
        mid = u[lo:hi, 1:-1, 1:-1]
        out[lo - z0:hi - z0, 1:-1, 1:-1] = (
            mid * c["invhxyz2"]
            + (u[lo:hi, 1:-1, :-2] + u[lo:hi, 1:-1, 2:]) * c["invhx2"]
            + (u[lo:hi, :-2, 1:-1] + u[lo:hi, 2:, 1:-1]) * c["invhy2"]
            + (u[lo - 1:hi - 1, 1:-1, 1:-1] + u[lo + 1:hi + 1, 1:-1, 1:-1])
            * c["invhz2"])
    return out


def compare(cfg: Dict, inputs, got) -> Dict[str, float]:
    """``stencil_rel_err``: the largest absolute difference between the
    output and the float64 reference, over the largest reference value."""
    u = np.asarray(inputs[0]).astype(np.float64)
    g = np.asarray(got)
    if g.shape != u.shape:
        return {"stencil_rel_err": float("inf")}
    c = coefficients(cfg)
    err, scale = 0.0, 0.0
    for z0 in range(0, u.shape[0], BLOCK):
        z1 = min(z0 + BLOCK, u.shape[0])
        want = _block(u, z0, z1, c)
        err = max(err, float(np.max(np.abs(g[z0:z1] - want))))
        scale = max(scale, float(np.max(np.abs(want))))
    return {"stencil_rel_err": err / scale}


def control(cfg: Dict, inputs):
    """The reference in the precision below the configuration's: the same
    stencil computed in bfloat16 on the device, returned as float32."""
    import jax
    import jax.numpy as jnp
    c = coefficients(cfg)
    b = jnp.bfloat16

    def f(u):
        u = u.astype(b)
        core = (u[1:-1, 1:-1, 1:-1] * b(c["invhxyz2"])
                + (u[1:-1, 1:-1, :-2] + u[1:-1, 1:-1, 2:]) * b(c["invhx2"])
                + (u[1:-1, :-2, 1:-1] + u[1:-1, 2:, 1:-1]) * b(c["invhy2"])
                + (u[:-2, 1:-1, 1:-1] + u[2:, 1:-1, 1:-1]) * b(c["invhz2"]))
        return jnp.pad(core, 1).astype(jnp.float32)
    return jax.jit(f)(inputs[0])
