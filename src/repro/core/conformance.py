"""Registry-wide differential conformance: every backend vs its oracle.

Godoy et al. (2023) score portability models on *validated* cross-backend
parity, not just speed; this module is the single source of that contract:

  * ``CASES`` gives every registry kernel one small, deterministic input
    (a kernel without a case FAILS conformance — coverage is mandatory);
  * ``oracle_tolerance(kernel, backend)`` says how closely a backend must
    match the kernel's oracle — ``"bitwise"`` where PR 3/4 promised it
    (sharded oracle arithmetic), fp tolerances everywhere else;
  * ``BITWISE_TWIN`` names backends that must reproduce *another backend's*
    output bit-for-bit: a ``shard_pallas`` composite runs the same Pallas
    kernel source sharded, so sharding must not change its output at all
    (checked against ``pallas_interpret`` whenever the composite actually
    runs in interpret mode);
  * ``conformance_pairs()`` derives the (kernel, backend) matrix from the
    live registry — never a hand-written list — so every future backend is
    covered the moment it registers;
  * ``check_backend(kernel, backend)`` runs one cell of that matrix,
    raising ``BackendUnavailableError`` (an explicit, reasoned skip for the
    caller) when either side cannot run on this host, and
    ``AssertionError`` on any mismatch.

``tests/test_backend_conformance.py`` parametrizes over the matrix on any
host (multi-device backends skip on a 1-device pytest process);
``repro.distributed.selftest`` walks the same matrix under 8 forced host
devices, so the sharded backends get identical coverage there.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Tuple, Union

import numpy as np

import repro.kernels  # noqa: F401  (registers every backend)
from repro.core.portable import registry

Tolerance = Union[str, Tuple[float, float]]  # "bitwise" | (rtol, atol)


# --------------------------------------------------------------------------
# cases: one small deterministic input per registry kernel.  Sizes satisfy
# every backend's *default* tile constraints (nx=128 lanes, ny % 64, pose /
# block-row multiples) and divide by the 2/4/8 shard grids.
# --------------------------------------------------------------------------
def _f32(a):
    import jax.numpy as jnp
    return jnp.asarray(a, jnp.float32)


def _stencil_case():
    u = np.random.default_rng(0).standard_normal((8, 64, 128))
    return (_f32(u),), {}


def _stream_case(nargs):
    r = np.random.default_rng(1)
    n = 1 << 17
    return tuple(_f32(r.standard_normal(n)) for _ in range(nargs)), {}


def _minibude_case():
    from repro.kernels.minibude import ops as mb_ops
    return mb_ops.make_deck(natpro=16, natlig=4, nposes=512, seed=0), {}


def _hf_case():
    from repro.kernels.hartree_fock import ref as hf_ref
    return (hf_ref.helium_lattice(8), hf_ref.initial_density(8)), {}


def _flash_case():
    r = np.random.default_rng(2)
    b, h, s, dh = 1, 2, 128, 64
    return tuple(_f32(r.standard_normal((b, h, s, dh)) * 0.5)
                 for _ in range(3)), {}


def _decode_case():
    """Single-query decode against a ring-buffer cache that exercises both
    hard edges at once: row 0 has wrapped (slot order != position order),
    row 1 has empty slots (pos -1 holes)."""
    import jax.numpy as jnp
    r = np.random.default_rng(4)
    b, h, kv, t, dh = 2, 4, 2, 128, 64
    q = _f32(r.standard_normal((b, 1, h, dh)) * 0.5)
    k = _f32(r.standard_normal((b, t, kv, dh)) * 0.5)
    v = _f32(r.standard_normal((b, t, kv, dh)) * 0.5)
    pos = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    pos[0, :5] += t                 # row 0: ring wrapped at slots 0..4
    pos[1, 41:] = -1                # row 1: cache only 41/128 full
    q_pos = np.asarray([[t + 5], [41]], np.int32)
    return (q, k, v, jnp.asarray(q_pos), jnp.asarray(pos)), {}


def _wkv_case():
    import jax.numpy as jnp
    r = np.random.default_rng(3)
    b, h, s, dh = 1, 2, 64, 32
    rr, kk, vv = (_f32(r.standard_normal((b, h, s, dh)) * 0.5)
                  for _ in range(3))
    lw = -jnp.exp(jnp.clip(_f32(r.standard_normal((b, h, s, dh))), -8, 1))
    u = _f32(r.standard_normal((h, dh)) * 0.5)
    return (rr, kk, vv, lw, u), {}


CASES: Dict[str, Callable[[], Tuple[tuple, dict]]] = {
    "stencil7": _stencil_case,
    "babelstream.copy": lambda: _stream_case(1),
    "babelstream.mul": lambda: _stream_case(1),
    "babelstream.add": lambda: _stream_case(2),
    "babelstream.triad": lambda: _stream_case(2),
    "babelstream.dot": lambda: _stream_case(2),
    "minibude.fasten": _minibude_case,
    "hartree_fock.twoel": _hf_case,
    "attention.flash": _flash_case,
    "attention.decode": _decode_case,
    "rwkv6.wkv": _wkv_case,
}

#: per-kernel default tolerance vs the oracle (from the families' own
#: validation suites)
ORACLE_TOL: Dict[str, Tolerance] = {
    "stencil7": (1e-5, 1e-5),
    "babelstream.copy": (1e-6, 1e-6),
    "babelstream.mul": (1e-6, 1e-6),
    "babelstream.add": (1e-6, 1e-6),
    "babelstream.triad": (1e-6, 1e-6),
    "babelstream.dot": (1e-4, 1e-3),
    "minibude.fasten": (2e-4, 2e-3),
    "hartree_fock.twoel": (1e-4, 1e-4),
    "attention.flash": (2e-4, 2e-4),
    "attention.decode": (2e-4, 2e-4),
    "rwkv6.wkv": (3e-4, 3e-4),
}

#: (kernel, backend) overrides — bitwise where PR 3/4 promised it: the
#: sharded-oracle backends apply the unchanged oracle arithmetic
BACKEND_TOL: Dict[Tuple[str, str], Tolerance] = {
    ("stencil7", "xla_shard"): "bitwise",
    ("babelstream.copy", "xla_shard"): "bitwise",
    ("babelstream.mul", "xla_shard"): "bitwise",
    ("babelstream.add", "xla_shard"): "bitwise",
    ("babelstream.triad", "xla_shard"): "bitwise",
    ("minibude.fasten", "xla_shard"): "bitwise",
    # the attention xla backends ARE the serving engine's historical
    # plain-XLA `attend` path (PR 6): registering them as oracles is the
    # contract that dispatch's default route stays bitwise-identical
    ("attention.flash", "xla"): "bitwise",
    ("attention.decode", "xla"): "bitwise",
}

#: backend -> backend whose output it must reproduce *bitwise* (the
#: composite runs the same kernel source — sharding must not change it).
#: dot and hartree_fock are excluded: psum changes their summation order.
BITWISE_TWIN: Dict[Tuple[str, str], str] = {
    ("stencil7", "shard_pallas"): "pallas_interpret",
    ("babelstream.copy", "shard_pallas"): "pallas_interpret",
    ("babelstream.mul", "shard_pallas"): "pallas_interpret",
    ("babelstream.add", "shard_pallas"): "pallas_interpret",
    ("babelstream.triad", "shard_pallas"): "pallas_interpret",
    ("minibude.fasten", "shard_pallas"): "pallas_interpret",
}


def oracle_tolerance(kernel: str, backend: str) -> Tolerance:
    return BACKEND_TOL.get((kernel, backend), ORACLE_TOL.get(kernel))


def conformance_pairs() -> List[Tuple[str, str]]:
    """Every (kernel, backend) cell of the live registry, sorted.  Derived,
    never hand-written: a backend registered tomorrow appears here today."""
    return [(name, b) for name in registry.names()
            for b in sorted(registry.get(name).backends)]


def _assert_match(kernel: str, backend: str, against: str, want: Any,
                  got: Any, tol: Tolerance) -> None:
    import jax

    def one(w, g):
        w, g = np.asarray(w), np.asarray(g)
        if tol == "bitwise":
            if not np.array_equal(w, g):
                bad = int(np.sum(w != g))
                raise AssertionError(
                    f"{kernel}[{backend}] is not bitwise equal to "
                    f"{against} ({bad}/{w.size} cells differ)")
        else:
            rtol, atol = tol
            np.testing.assert_allclose(
                g.astype(np.float64), w.astype(np.float64), rtol=rtol,
                atol=atol,
                err_msg=f"{kernel}[{backend}] vs {against}")

    jax.tree.map(one, want, got)


@functools.lru_cache(maxsize=None)
def _case_and_oracle(kernel: str):
    """Deterministic case inputs + oracle output, computed once per kernel
    (the matrix walk compares many backends against the same oracle cell).
    Exceptions — including ``BackendUnavailableError`` from an oracle that
    cannot run here — are not cached and re-raise per call."""
    k = registry.get(kernel)
    args, kwargs = CASES[kernel]()
    want = k._require_available(k.oracle)(*args, **kwargs)
    return args, kwargs, want


def check_backend(kernel: str, backend: str) -> None:
    """Run one conformance cell: ``backend`` vs the kernel's oracle (and
    its bitwise twin, when one is declared and running the same mode).

    Raises ``KeyError`` for an unregistered kernel/backend,
    ``AssertionError`` for a missing case or a mismatch, and
    ``BackendUnavailableError`` when this host cannot run the pair — the
    caller turns that into an explicit, reasoned skip.
    """
    k = registry.get(kernel)
    case = CASES.get(kernel)
    if case is None:
        raise AssertionError(
            f"kernel {kernel!r} has no conformance case — every registered "
            f"kernel must add one to repro.core.conformance.CASES")
    tol = oracle_tolerance(kernel, backend)
    if tol is None:
        raise AssertionError(
            f"kernel {kernel!r} has no conformance tolerance — add it to "
            f"repro.core.conformance.ORACLE_TOL")
    args, kwargs, want = _case_and_oracle(kernel)
    got = k._require_available(backend)(*args, **kwargs)
    _assert_match(kernel, backend, k.oracle, want, got, tol)

    twin = BITWISE_TWIN.get((kernel, backend))
    if twin is None:
        return
    from repro.distributed.shard_pallas import default_interpret
    tb = k.backends.get(twin)
    # the twin claim only binds when the composite actually runs the
    # interpret path the twin runs (on TPU it runs the compiled kernel)
    if tb is not None and tb.is_available() and default_interpret():
        ref = tb(*args, **kwargs)
        _assert_match(kernel, backend, twin, ref, got, "bitwise")
