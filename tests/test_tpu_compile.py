"""Main-path Pallas kernels compiled for a described TPU v5e, chip absent.

The TPU compiler is installed with JAX, so each kernel is lowered and
compiled at the paper's sizes (and granite-3-8b attention widths) for one
chip of a described ``v5e:2x2`` topology.  Nothing runs: a pass proves the
chip's compiler accepts the kernel — the refusals interpret mode cannot see
(unaligned blocks, unlowerable primitives, VMEM overuse) fail here.  Every
compile must contain the Mosaic kernel (``tpu_custom_call``); an XLA
fallback would not.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.babelstream import ops as stream_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.hartree_fock import ops as hf_ops
from repro.kernels.minibude import ops as mb_ops
from repro.kernels.stencil7 import ops as s7_ops

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


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def test_stencil7_l512(one_chip):
    _compile(one_chip, s7_ops.laplacian_pallas, [((512, 512, 512), F32)])


@pytest.mark.parametrize("fn", [stream_ops.triad_pallas,
                                stream_ops.dot_pallas],
                         ids=["triad", "dot"])
def test_babelstream_2pow25(one_chip, fn):
    _compile(one_chip, fn, [((1 << 25,), F32)] * 2)


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
