"""Ahead-of-time compiles of the chip's hot path for a described TPU v5e.

Nothing here runs on a chip: XLA:TPU and Mosaic compile each program for a
v5e that is described, not attached, and refuse what the chip would refuse
(64-bit dots and all-reduces, unaligned blocks, unsupported primitives).
Widths are those of ``chip_smoke.py`` at twice its relation: 16,384 tuples
at the default codec (W=12, A=69, four string columns) — ``chip_smoke.py``
holds 8,192 on one chip — shared to the 27 clouds it uses (a W=12 fetch opens
a degree-25 sharing, and ``verify=`` needs two more) and to 25; and the
gemma3-1b embedding table (V=262,144, D=1,152) behind a 256-token decode
step.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU compiler library, and every xdist worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

import repro  # noqa: F401  (process-wide x64, as the program runs)
from repro.api import planner
from repro.api.backends import jnp_ripple_segment
from repro.core import field, shamir
from repro.core.mesh_dispatch import MeshDispatcher
from repro.kernels.aa_match import aa_match_batch_pallas, aa_slide_batch_pallas
from repro.kernels.ripple import ripple_segment_pallas
from repro.kernels.ss_matmul import (share_onehot_pallas, ss_matmul_pallas,
                                     ss_matmul_tall_pallas)
from repro.launch.mesh import make_mesh

N_TUPLES, W, A, M_COLS = 16_384, 12, 69, 4
CLOUDS = (25, 27)
TOKENS, VOCAB, D_MODEL, C_EMBED = 256, 262_144, 1_152, 4
# the lineitem.range cell: a 14-bit range group of 8 requests (16 chains)
# over 8,192 tuples at 27 clouds, in the planner's segments (13, then 1)
RANGE_C, RANGE_S, RANGE_N, RANGE_T = 27, 16, 8_192, 14
RANGE_EVERY = planner.choose_reduce_every(RANGE_C, 1, RANGE_T)
FETCH_ROWS = 256
U32 = jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _kernel(fn, *args):
    text = _compile(fn, *args)
    assert "tpu_custom_call" in text       # Mosaic, not the interpreter
    return text


def test_ss_matmul_fetch_compiles(one_chip):
    a = jax.ShapeDtypeStruct((FETCH_ROWS, N_TUPLES), U32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((N_TUPLES, M_COLS * W * A), U32,
                             sharding=one_chip)
    _kernel(lambda x, y: ss_matmul_pallas(x, y, interpret=False), a, b)


def test_ss_matmul_tall_embedding_compiles(one_chip):
    a = jax.ShapeDtypeStruct((TOKENS, VOCAB), U32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((VOCAB, D_MODEL), U32, sharding=one_chip)
    _kernel(lambda x, y: ss_matmul_tall_pallas(x, y, interpret=False), a, b)


def test_share_onehot_compiles(one_chip):
    tok = jax.ShapeDtypeStruct((TOKENS,), jnp.int32, sharding=one_chip)
    a1 = jax.ShapeDtypeStruct((TOKENS, VOCAB), U32, sharding=one_chip)
    _kernel(lambda t, x: share_onehot_pallas(t, x, n_shares=C_EMBED,
                                             interpret=False), tok, a1)


@pytest.mark.parametrize("c", CLOUDS)
def test_aa_match_batch_compiles(one_chip, c):
    col = jax.ShapeDtypeStruct((c, N_TUPLES, W, A), U32, sharding=one_chip)
    pat = jax.ShapeDtypeStruct((c, W, A), U32, sharding=one_chip)
    _kernel(lambda x, y: aa_match_batch_pallas(x, y, interpret=False),
            col, pat)


@pytest.mark.parametrize("c", CLOUDS)
@pytest.mark.parametrize("k", [1, 3, W])
def test_aa_slide_batch_compiles(one_chip, k, c):
    col = jax.ShapeDtypeStruct((c, N_TUPLES, W, A), U32, sharding=one_chip)
    pat = jax.ShapeDtypeStruct((c, k, A), U32, sharding=one_chip)
    _kernel(lambda x, y: aa_slide_batch_pallas(x, y, interpret=False),
            col, pat)


@pytest.mark.parametrize("init", [True, False])
def test_ripple_segment_compiles(one_chip, init):
    lanes = CLOUDS[-1] * 2 * N_TUPLES              # both Eq. 2 directions
    a = jax.ShapeDtypeStruct((2, lanes), U32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((lanes,), U32, sharding=one_chip)
    _kernel(lambda x, y, z: ripple_segment_pallas(x, y, z, init=init,
                                                  interpret=False), a, a, c)


@pytest.mark.parametrize("k, carry", [(RANGE_EVERY, False),
                                      (RANGE_T - RANGE_EVERY, True)])
def test_jnp_ripple_segment_compiles(one_chip, k, carry):
    assert RANGE_EVERY == 13
    bits = jax.ShapeDtypeStruct((RANGE_C, RANGE_S, RANGE_N, k), U32,
                                sharding=one_chip)
    if carry:
        c_in = jax.ShapeDtypeStruct((RANGE_C, RANGE_S, RANGE_N), U32,
                                    sharding=one_chip)
        _compile(jnp_ripple_segment, bits, bits, c_in)
    else:
        _compile(lambda a, b: jnp_ripple_segment(a, b), bits, bits)


def test_carry_reshare_compiles(one_chip):
    # the carry after 13 bits is degree 26: all 27 clouds' shares re-share
    key = jax.ShapeDtypeStruct((2,), U32, sharding=one_chip)
    carry = jax.ShapeDtypeStruct((RANGE_C, RANGE_S, RANGE_N), U32,
                                 sharding=one_chip)
    _compile(lambda k, v: shamir._reshare(k, v, need=2 * RANGE_EVERY + 1,
                                          target_degree=1), key, carry)


@pytest.mark.parametrize("shape", [
    *(((c, FETCH_ROWS, N_TUPLES), (c, N_TUPLES, M_COLS * W * A))  # fetch
      for c in CLOUDS),
    ((C_EMBED, TOKENS, VOCAB), (C_EMBED, VOCAB, D_MODEL)),        # embed
    # lineitem.range: 4 Q1 match bits against a 26-bit column's two limbs
    ((RANGE_C, 4, RANGE_N), (RANGE_C, RANGE_N, 2)),
])
def test_field_matmul_compiles(one_chip, shape):
    a = jax.ShapeDtypeStruct(shape[0], U32, sharding=one_chip)
    b = jax.ShapeDtypeStruct(shape[1], U32, sharding=one_chip)
    text = _compile(field.matmul, a, b)
    dots = [ln for ln in text.splitlines() if "convolution(" in ln]
    assert dots and all(ln.split("=")[1].lstrip().startswith("s32")
                        for ln in dots)           # exact int8 -> int32 dots


def test_field_matmul_resident_digits_compiles(one_chip):
    # the embedding table as the four int8 digits kept beside it: the
    # same exact int8 -> int32 dots, and a split program with no temps
    a = jax.ShapeDtypeStruct((C_EMBED, TOKENS, VOCAB), U32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((C_EMBED, VOCAB, D_MODEL), U32,
                                 sharding=one_chip)
    digits = tuple(jax.ShapeDtypeStruct(table.shape, jnp.int8,
                                        sharding=one_chip) for _ in range(4))
    text = _compile(field.matmul, a, digits)
    dots = [ln for ln in text.splitlines() if "convolution(" in ln]
    assert dots and all(ln.split("=")[1].lstrip().startswith("s32")
                        for ln in dots)
    split = jax.jit(field.table_digits).lower(table).compile()
    assert split.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("n_dev", [1, 4])
def test_mesh_reduce_compiles(topo, n_dev):
    mesh = make_mesh((n_dev, 1), ("data", "model"),
                     devices=topo.devices[:n_dev])
    disp = MeshDispatcher(mesh, collect_hlo=False)
    shape = (n_dev, CLOUDS[-1], 1, M_COLS * W * A)        # one partial per data shard
    fn = disp._sum_fn(shape, "uint32")
    spec = NamedSharding(mesh, PartitionSpec("data"))
    text = _compile(fn, jax.ShapeDtypeStruct(shape, U32, sharding=spec))
    if n_dev > 1:
        assert "all-reduce" in text
