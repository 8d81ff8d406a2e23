"""PR-6 attention registry dispatch: decode kernel vs ``attend``, backend
resolution (env var precedence, unavailable-backend raise), tuned-param
injection, and the per-backend batched↔unbatched serving bit-match.

The decode oracle contract is *bitwise*: ``attention.decode``'s xla backend
is literally the plain-XLA ``attend_xla`` path serving has always run (both
sides jitted — eager-vs-jit FMA contraction differs, so bitwise comparisons
must compile both sides).  Pallas variants run in interpret mode on the host
at the documented fp tolerance.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels  # noqa: F401  (registers attention.* backends)
from repro.configs import get_config
from repro.core import conformance, tuning
from repro.core.portable import BackendUnavailableError, registry
from repro.models import attention as A
from repro.models import transformer as T
from repro.serving import Request, ServingEngine
from repro.training import serve_step as SS

RTOL = ATOL = 2e-4      # documented pallas-vs-oracle tolerance


def _decode_inputs(seed, *, b=2, h=4, kv=2, t=128, dh=32, wrap=0, holes=None,
                   q_pos=None):
    """Model-native decode call: q (B,1,H,Dh), ring cache k/v (B,T,Kv,Dh)."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((b, 1, h, dh)) * 0.5, jnp.float32)
    k = jnp.asarray(r.standard_normal((b, t, kv, dh)) * 0.5, jnp.float32)
    v = jnp.asarray(r.standard_normal((b, t, kv, dh)) * 0.5, jnp.float32)
    pos = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    if wrap:
        pos[:, :wrap] += t          # ring wrapped: low slots hold new tokens
    if holes is not None:
        pos[:, holes:] = -1         # cache only partially filled
    if q_pos is None:
        q_pos = pos.max(axis=1) + 1
    qp = jnp.asarray(np.asarray(q_pos, np.int32).reshape(b, 1))
    return q, k, v, qp, jnp.asarray(pos)


def _ref(q, k, v, qp, kp, *, kv, window=0):
    fn = jax.jit(lambda *a: A.attend_xla(*a, n_kv_heads=kv, causal=True,
                                         window=window))
    return fn(q, k, v, qp, kp)


# --------------------------------------------------------------------------
# registry surface
# --------------------------------------------------------------------------
def test_attention_kernels_registered_with_tunables():
    for name, params in [("attention.flash", {"bq", "bk"}),
                         ("attention.decode", {"bkv"})]:
        k = registry.get(name)
        assert {"xla", "pallas", "pallas_interpret"} <= set(k.backends)
        assert k.oracle == "xla"
        for b in ("pallas", "pallas_interpret"):
            space = k.tunable_space(b)
            assert space is not None and set(space.params) == params
        # conformance coverage is mandatory: deregistering either kernel,
        # or dropping its case, fails here and in the matrix suite
        assert name in conformance.CASES
        assert name in conformance.ORACLE_TOL
        assert conformance.oracle_tolerance(name, "xla") == "bitwise"


# --------------------------------------------------------------------------
# decode kernel vs attend
# --------------------------------------------------------------------------
@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("window", [0, 8])
def test_decode_xla_bitwise_vs_attend(h, kv, window):
    k = registry.get("attention.decode")
    q, kc, vc, qp, kp = _decode_inputs(10 + h + kv, h=h, kv=kv, t=64)
    want = _ref(q, kc, vc, qp, kp, kv=kv, window=window)
    got = k(q, kc, vc, qp, kp, backend="xla", window=window)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (4, 1)])
def test_decode_interpret_gqa_ratios(h, kv):
    k = registry.get("attention.decode")
    q, kc, vc, qp, kp = _decode_inputs(20 + h + kv, h=h, kv=kv)
    want = _ref(q, kc, vc, qp, kp, kv=kv)
    got = k(q, kc, vc, qp, kp, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def test_decode_interpret_ring_wraparound_and_window():
    k = registry.get("attention.decode")
    q, kc, vc, qp, kp = _decode_inputs(3, wrap=5)
    for window in (0, 16):
        want = _ref(q, kc, vc, qp, kp, kv=2, window=window)
        got = k(q, kc, vc, qp, kp, backend="pallas_interpret", window=window,
                bkv=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)


def test_decode_interpret_leftpad_holes():
    """Empty slots (pos -1, the leftpad drop semantics) never attended."""
    k = registry.get("attention.decode")
    q, kc, vc, qp, kp = _decode_inputs(4, holes=41, q_pos=[41, 41])
    want = _ref(q, kc, vc, qp, kp, kv=2)
    got = k(q, kc, vc, qp, kp, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    # and the garbage in the holes genuinely doesn't leak: poisoning the
    # masked slots changes nothing
    kc2 = kc.at[:, 41:].set(1e4)
    vc2 = vc.at[:, 41:].set(-1e4)
    got2 = k(q, kc2, vc2, qp, kp, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))


def test_decode_cache_len_one():
    k = registry.get("attention.decode")
    q, kc, vc, qp, kp = _decode_inputs(5, t=1, q_pos=[0, 0])
    want = _ref(q, kc, vc, qp, kp, kv=2)
    got = k(q, kc, vc, qp, kp, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def test_flash_interpret_leftpad_positions():
    """Prefill kernel in position mode: leftpad -1 rows masked out."""
    k = registry.get("attention.flash")
    r = np.random.default_rng(6)
    b, h, kv, s, dh, pad = 2, 4, 2, 64, 32, 9
    q = jnp.asarray(r.standard_normal((b, h, s, dh)) * 0.5, jnp.float32)
    kc = jnp.asarray(r.standard_normal((b, kv, s, dh)) * 0.5, jnp.float32)
    vc = jnp.asarray(r.standard_normal((b, kv, s, dh)) * 0.5, jnp.float32)
    pos = np.tile(np.arange(s, dtype=np.int32) - pad, (b, 1))
    pos[pos < 0] = -1
    pos = jnp.asarray(pos)
    want = k(q, kc, vc, pos, pos, backend="xla", causal=True, window=0)
    got = k(q, kc, vc, pos, pos, backend="pallas_interpret", causal=True,
            window=0, bq=32, bk=32)
    # pad-query rows are garbage by contract on both sides; compare real rows
    np.testing.assert_allclose(np.asarray(got)[:, :, pad:],
                               np.asarray(want)[:, :, pad:],
                               rtol=RTOL, atol=ATOL)


# --------------------------------------------------------------------------
# backend resolution + dispatch
# --------------------------------------------------------------------------
def test_resolve_precedence_and_fallback(monkeypatch):
    monkeypatch.delenv(A.ATTN_BACKEND_ENV, raising=False)
    assert A.resolve_attention_backend("decode", None) == "xla"
    assert A.resolve_attention_backend("decode", "auto") == "xla"
    assert A.resolve_attention_backend("prefill", "xla") == "xla"
    ik = registry.get("attention.decode")
    if ik.backends["pallas_interpret"].is_available():
        assert A.resolve_attention_backend(
            "decode", "pallas_interpret") == "pallas_interpret"
    if not ik.backends["pallas"].is_available():
        # requested-but-unavailable raises; it never becomes the oracle
        with pytest.raises(BackendUnavailableError, match="pallas"):
            A.resolve_attention_backend("decode", "pallas")
    # env var wins over the argument
    monkeypatch.setenv(A.ATTN_BACKEND_ENV, "xla")
    assert A.resolve_attention_backend("decode", "pallas_interpret") == "xla"
    monkeypatch.delenv(A.ATTN_BACKEND_ENV)
    with pytest.raises(KeyError):
        A.resolve_attention_backend("decode", "no_such_backend")
    with pytest.raises(KeyError):
        A.resolve_attention_backend("no_such_kind", "xla")


@pytest.mark.parametrize("name", ["attention.flash", "attention.decode"])
def test_compiled_pallas_never_interprets(name):
    """``pallas`` means the compiled kernel: off-TPU a direct call fails in
    the compiler instead of quietly running the interpreter."""
    q, kc, vc, qp, kp = _decode_inputs(13, t=64)
    if name == "attention.flash":
        q, kc, vc = (jnp.moveaxis(x, 2, 1) for x in (q, kc, vc))
    if jax.devices()[0].platform == "tpu":
        pytest.skip("compiled Pallas runs here")
    with pytest.raises(ValueError, match="interpret"):
        registry.get(name)(q, kc, vc, qp, kp, backend="pallas")


def test_attend_dispatch_routes_and_falls_back(monkeypatch):
    monkeypatch.delenv(A.ATTN_BACKEND_ENV, raising=False)
    q, kc, vc, qp, kp = _decode_inputs(7, t=64)
    want = _ref(q, kc, vc, qp, kp, kv=2)

    A.reset_dispatch_log()
    got = A.attend(q, kc, vc, qp, kp, n_kv_heads=2, causal=True,
                   backend="pallas_interpret")
    log = A.dispatch_log()["decode"]
    assert log["backend"] == "pallas_interpret"
    assert log["tuning"] == "miss-default"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)

    # default request: status-quo XLA path, bitwise
    A.reset_dispatch_log()
    got = jax.jit(lambda *a: A.attend(*a, n_kv_heads=2, causal=True)
                  )(q, kc, vc, qp, kp)
    assert A.dispatch_log()["decode"]["backend"] == "xla"
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # a cache length no block size divides falls back to XLA with a reason
    q2, kc2, vc2, qp2, kp2 = _decode_inputs(8, t=300)
    A.reset_dispatch_log()
    got2 = A.attend(q2, kc2, vc2, qp2, kp2, n_kv_heads=2, causal=True,
                    backend="pallas_interpret")
    log = A.dispatch_log()["decode"]
    assert log["backend"] == "xla" and "fallback" in log
    np.testing.assert_array_equal(
        np.asarray(got2), np.asarray(_ref(q2, kc2, vc2, qp2, kp2, kv=2)))

    # ring-wrapped causal prefill (k_index_aligned=False) stays on XLA
    r = np.random.default_rng(9)
    qq = jnp.asarray(r.standard_normal((1, 32, 4, 16)), jnp.float32)
    kk = jnp.asarray(r.standard_normal((1, 32, 2, 16)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32)[None], (1, 32))
    A.reset_dispatch_log()
    A.attend(qq, kk, kk, pos, pos, n_kv_heads=2, causal=True,
             backend="pallas_interpret", k_index_aligned=False)
    log = A.dispatch_log()["prefill"]
    assert log["backend"] == "xla" and "fallback" in log


def test_tuned_param_injection(monkeypatch, tmp_path):
    """A planted cache entry is injected at dispatch and reported with its
    search provenance."""
    monkeypatch.delenv(A.ATTN_BACKEND_ENV, raising=False)
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path / "tuning.json"))
    k = registry.get("attention.decode")
    q, kc, vc, qp, kp = _decode_inputs(11, t=128)
    key = tuning.make_key(k, q, kc, vc, qp, kp,
                          backend="pallas_interpret", window=0)
    tuning.TuningCache().put(key, {"bkv": 64}, 1.0, search="exhaustive")

    A.reset_dispatch_log()
    got = A.attend(q, kc, vc, qp, kp, n_kv_heads=2, causal=True,
                   backend="pallas_interpret")
    log = A.dispatch_log()["decode"]
    assert log["tuning"] == "exhaustive"
    assert log["params"] == {"bkv": 64}
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_ref(q, kc, vc, qp, kp, kv=2)),
                               rtol=RTOL, atol=ATOL)

    # a different shape misses the cache -> declared defaults
    q2, kc2, vc2, qp2, kp2 = _decode_inputs(12, t=64)
    A.reset_dispatch_log()
    A.attend(q2, kc2, vc2, qp2, kp2, n_kv_heads=2, causal=True,
             backend="pallas_interpret")
    assert A.dispatch_log()["decode"]["tuning"] == "miss-default"


# --------------------------------------------------------------------------
# per-backend serving bit-match (batched engine vs unbatched generate)
# --------------------------------------------------------------------------
CFG = get_config("granite-3-8b", smoke=True)


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_engine_greedy_bitmatch_per_backend(params, backend, monkeypatch):
    monkeypatch.delenv(A.ATTN_BACKEND_ENV, raising=False)
    rng = np.random.default_rng(2)
    lens = [3, 9, 12, 5]
    prompts = [rng.integers(2, CFG.vocab_size, L).astype(np.int32)
               for L in lens]
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=16, attn_backend=backend)
    assert eng.attn_backends == {"prefill": backend, "decode": backend}
    done = eng.run([Request(uid=i, prompt=p, max_new_tokens=5,
                            arrival_time=0.0)
                    for i, p in enumerate(prompts)])
    assert len(done) == len(prompts)
    for r in sorted(done, key=lambda r: r.uid):
        want = SS.generate(params, CFG, jnp.asarray(prompts[r.uid][None]),
                           max_new_tokens=5, cache_len=32,
                           attn_backend=backend)
        assert r.generated == list(np.asarray(want[0])), \
            f"slot-batched decode diverged from unbatched under {backend}"
