"""Plain reference of one BabelStream iteration, in upstream's order:

    c = a;  b = s*c;  c = a + b;  a = b + s*c;  sum = a.b

computed in float64 with NumPy on the host from the same inputs; it shares
no code with the program.
"""

from typing import Dict

import numpy as np

#: upstream's start values of a and b, and a scale for c (upstream starts c
#: at 0.0, where a copy that leaves c alone could not show)
SCALES = (0.1, 0.2, 0.3)


def make_inputs(cfg: Dict, key):
    """a, b and c of ``n`` elements, each uniform in [0.5, 1.5) times its
    scale, made on the device from ``key``."""
    import jax
    import jax.numpy as jnp
    n, dtype = int(cfg["n"]), jnp.dtype(cfg["dtype"])

    def f(key):
        return tuple(jax.random.uniform(k, (n,), dtype, 0.5, 1.5)
                     * jnp.asarray(s, dtype)
                     for k, s in zip(jax.random.split(key, 3), SCALES))
    return jax.jit(f)(key)


def chain(a: np.ndarray, scalar: float):
    """One iteration in float64 from ``a``; b and c are written before they
    are read.  Returns the four arrays written, then the sum."""
    a = a.astype(np.float64)
    c = a
    b = scalar * c
    c2 = a + b
    a2 = b + scalar * c2
    return (c, b, c2, a2), float(np.dot(a2, b))


def compare(cfg: Dict, inputs, got) -> Dict[str, float]:
    """``stream_rel_err``: the largest absolute difference over the four
    arrays written, over the largest reference value among them;
    ``dot_rel_err``: the sum's relative difference (the inputs are
    positive, so the sum has no cancellation)."""
    want, want_dot = chain(np.asarray(inputs[0]), float(cfg["scalar"]))
    *arrays, dot = got
    err, scale = 0.0, 0.0
    for g, w in zip(arrays, want):
        g = np.asarray(g)
        if g.shape != w.shape:
            return {"stream_rel_err": float("inf"),
                    "dot_rel_err": float("inf")}
        err = max(err, float(np.max(np.abs(g - w))))
        scale = max(scale, float(np.max(np.abs(w))))
    return {"stream_rel_err": err / scale,
            "dot_rel_err": abs(float(dot) - want_dot) / abs(want_dot)}


def control(cfg: Dict, inputs):
    """The chain in the precision below the configuration's: bfloat16
    arrays and a bfloat16 sum, computed on the device, returned as
    float32."""
    import jax
    import jax.numpy as jnp
    bf = jnp.bfloat16
    s = jnp.asarray(cfg["scalar"], bf)

    def f(a):
        a = a.astype(bf)
        c = a
        b = s * c
        c2 = a + b
        a2 = b + s * c2
        dot = jnp.sum(a2 * b, dtype=bf)
        return tuple(x.astype(jnp.float32) for x in (c, b, c2, a2, dot))
    return jax.jit(f)(inputs[0])
