"""Main-path Pallas kernels compiled for a described TPU v5e, chip absent.

The TPU compiler is installed with JAX, so each kernel is lowered and
compiled at the paper's sizes (and granite-3-8b attention widths) for one
chip of a described ``v5e:2x2`` topology.  Nothing runs: a pass proves the
chip's compiler accepts the kernel — the refusals interpret mode cannot see
(unaligned blocks, unlowerable primitives, VMEM overuse) fail here.  Every
compile must contain the Mosaic kernel (``tpu_custom_call``); an XLA
fallback would not.  The serving engine's own programs are compiled the
same way, to show that they update the KV cache in place.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.babelstream import ops as stream_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.hartree_fock import ops as hf_ops
from repro.kernels.minibude import ops as mb_ops
from repro.kernels.stencil7 import ops as s7_ops
from repro.models import transformer as T
from repro.serving import engine as E

# granite-3-8b attention widths
H, KV, DH = 32, 8, 128
CACHE_LEN, BUCKET, SLOTS = 1024, 128, 4


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(sharding, fn, shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def test_stencil7_l512(one_chip):
    _compile(one_chip, s7_ops.laplacian_pallas, [((512, 512, 512), F32)])


def test_stencil7_l1024(one_chip):
    """The paper's other size: 4 MiB planes overflow the VMEM budget, so
    the default takes 512-row tiles with their halo copies."""
    _compile(one_chip, s7_ops.laplacian_pallas, [((1024, 1024, 1024), F32)])


@pytest.mark.parametrize("op,n_in", [("copy", 1), ("mul", 1), ("add", 2),
                                     ("triad", 2), ("dot", 2)],
                         ids=["copy", "mul", "add", "triad", "dot"])
def test_babelstream_2pow25(one_chip, op, n_in):
    """Each kernel of the upstream iteration, named in the compiled module
    after its own wrapper, as the device trace names it."""
    fn = getattr(stream_ops, f"{op}_pallas")
    text = _compile(one_chip, fn, [((1 << 25,), F32)] * n_in)
    assert f"%{op}_pallas" in text


def test_minibude_bm1(one_chip):
    deck = jax.eval_shape(mb_ops.make_deck)
    _compile(one_chip, mb_ops.fasten_pallas,
             [(a.shape, a.dtype) for a in deck])


def test_hartree_fock_a64(one_chip):
    _compile(one_chip, hf_ops.fock_pallas, [((64, 3), F32), ((64, 64), F32)])


@pytest.mark.parametrize("with_pos", [True, False],
                         ids=["positions", "index"])
def test_flash_prefill_granite(one_chip, with_pos):
    shapes = [((1, H, BUCKET, DH), BF16), ((1, KV, CACHE_LEN, DH), BF16),
              ((1, KV, CACHE_LEN, DH), BF16)]
    if with_pos:
        shapes += [((1, BUCKET), I32), ((1, CACHE_LEN), I32)]
    _compile(one_chip, fa_ops.flash_pallas, shapes)


def test_decode_granite_four_slots(one_chip):
    _compile(one_chip, fa_ops.decode_pallas,
             [((SLOTS, 1, H, DH), BF16), ((SLOTS, CACHE_LEN, KV, DH), BF16),
              ((SLOTS, CACHE_LEN, KV, DH), BF16), ((SLOTS, 1), I32),
              ((SLOTS, CACHE_LEN), I32)])


def test_decode_reads_the_cache_as_stored(one_chip):
    """At the serving cell's 24 slots x 2048 positions the decode kernel
    takes K and V as the cache stores them: one Mosaic kernel, and no
    instruction but the parameters makes a whole K or V, neither
    transposed (B, Kv, T, Dh) nor copied (B, T, Kv, Dh)."""
    slots, cache_len = 24, 2048
    text = _compile(one_chip, fa_ops.decode_pallas,
                    [((slots, 1, H, DH), BF16),
                     ((slots, cache_len, KV, DH), BF16),
                     ((slots, cache_len, KV, DH), BF16), ((slots, 1), I32),
                     ((slots, cache_len), I32)])
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    wholes = [f"bf16[{slots},{KV},{cache_len},{DH}]",
              f"bf16[{slots},{cache_len},{KV},{DH}]"]
    made = [ln for ln in text.splitlines()
            if any(f"= {w}" in ln or f"= ({w}" in ln for w in wholes)
            and " parameter(" not in ln]
    assert not made, made


@pytest.mark.parametrize("program", ["prefill-128", "prefill-1536",
                                     "decode"])
def test_engine_programs_update_the_cache_in_place(one_chip, monkeypatch,
                                                   program):
    """The serving engine's own programs at the granite cell's slots and
    cache length (two layers: the copy shows at any depth), prefill at the
    cell's smallest and largest bucket, take the KV cache over: the whole
    cache is aliased to the output, and no K or V leaf is copied whole into
    a fresh buffer."""
    slots, cache_len, buckets = 24, 2048, (128, 1536)
    cfg = dataclasses.replace(get_config("granite-3-8b"), n_layers=2,
                              param_dtype="bfloat16")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    with monkeypatch.context() as m:       # the engine's cache, shapes only
        made = E.init_caches
        m.setattr(E, "init_caches", lambda *a, **k:
                  jax.eval_shape(lambda: made(*a, **k)))
        eng = E.ServingEngine(params, cfg, num_slots=slots,
                              cache_len=cache_len, prefill_buckets=buckets)
    caches = on_chip(eng.caches)
    if program.startswith("prefill"):
        bucket = int(program.split("-")[1])
        args = [on_chip(params), arg((1, bucket), I32), arg((1,), I32),
                arg((), I32), arg((2,), jnp.uint32), caches]
        fn = eng._prefill
    else:
        args = [on_chip(params), arg((slots, 1), I32), arg((slots, 1), I32),
                arg((slots, 2), jnp.uint32), caches]
        fn = eng._decode
    compiled = fn.lower(*args).compile()

    leaves = jax.tree.leaves(caches)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    text = compiled.as_text()
    for a in leaves:
        if a.dtype == BF16:                # K and V: all but 0.1 % of it
            whole = f"bf16[{','.join(map(str, a.shape))}]"
            copies = [ln for ln in text.splitlines()
                      if f"= {whole}" in ln and " copy(" in ln]
            assert not copies, copies
