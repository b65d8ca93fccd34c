"""AOT compiles of the served kernels for a described TPU v5e.

The chip's compiler is installed here, and it compiles for a chip that
is described and not attached, so these tests catch what interpret mode
cannot (tile alignment, VMEM limits, shard_map varying-axes metadata)
at no chip time. Shapes are the BASELINE dense-PIR cell: 2^20 records x
256 B, 128-key batches. Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under several pytest
workers the others would otherwise fail to collect. The compile cache
is off around the compiles (an AOT entry written for a described chip
cannot be read back here).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

U32 = jnp.uint32
NUM_RECORDS, RECORD_WORDS = 1 << 20, 64  # 2^20 x 256 B
KEY_GROUPS = 128 // 32  # 128-key batch
TILE_LANES = 1024  # ops/expand_planes_pallas.py:_TILE_LANES at kg=4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("num_queries", [128, 256])
def test_pallas2_inner_product_compiles(one_chip, num_queries):
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        xor_inner_product_pallas2_staged,
    )

    db = jax.ShapeDtypeStruct(
        (32, NUM_RECORDS // 32, RECORD_WORDS), U32, sharding=one_chip
    )
    sel = jax.ShapeDtypeStruct(
        (num_queries, NUM_RECORDS // 128, 4), U32, sharding=one_chip
    )
    _compile(xor_inner_product_pallas2_staged, db, sel)


@pytest.mark.parametrize(
    "kernel,lanes",
    [
        ("level", KEY_GROUPS),  # entry level: one narrow call
        ("level", 256),  # a middle level (2^6 nodes x 4 key groups)
        ("value", 256),
    ],
)
def test_auto_expansion_kernels_compile(one_chip, kernel, lanes):
    """The kernels of the TPU auto level-kernel set at serving widths;
    `TPU_AUTO_LEVEL_KERNEL` names that set. Levels wider than one tile
    run as calls of exactly `TILE_LANES` lanes; that width compiles too
    (PR 21 rehearsal) but takes ~80 s per kernel on this host, so the
    cases here stop at 256 lanes (~5 s)."""
    from distributed_point_functions_tpu.ops import expand_planes_pallas as epp
    from distributed_point_functions_tpu.pir import dense_eval_planes as dep

    assert dep.TPU_AUTO_LEVEL_KERNEL == "pallas"
    assert epp._pick_tile(KEY_GROUPS << 13, KEY_GROUPS) == TILE_LANES

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, U32, sharding=one_chip)

    state, ctrl = shape(16, 8, lanes), shape(lanes)
    planes = shape(16, 8, KEY_GROUPS)
    if kernel == "level":
        bits = shape(KEY_GROUPS)
        _compile(epp.expand_level_planes_pallas, state, ctrl, planes,
                 bits, bits)
    else:
        _compile(epp.value_hash_planes_pallas, state, ctrl, planes)


def test_streaming_pallas2_accumulate_in_shard_map_compiles(topo):
    """The mesh serving scan's accumulate: a varying carry XOR-folding
    the pallas2 kernel over this shard's database chunks, inside a
    shard_map over a 2x2 (shard x key) mesh of described devices."""
    from distributed_point_functions_tpu.ops.inner_product import (
        varying_zeros,
    )
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        xor_inner_product_pallas2_accumulate,
    )

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("shard", "key"))
    cut = 4  # 16 chunks of 2^16 records, 8 per shard
    chunk_records = NUM_RECORDS >> cut
    vma = ("shard", "key")

    def step(db_chunks, sel_chunks):
        def body(acc, xs):
            db_c, sel_c = xs
            return xor_inner_product_pallas2_accumulate(
                acc, db_c, sel_c, vma=vma
            ), None

        acc0 = varying_zeros(
            (sel_chunks.shape[1], RECORD_WORDS), db_chunks, sel_chunks
        )
        acc, _ = jax.lax.scan(body, acc0, (db_chunks, sel_chunks))
        return acc[None]

    mapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P("shard"), P("shard", "key")),
        out_specs=P("shard", "key"),
    )
    db = jax.ShapeDtypeStruct(
        (1 << cut, 32, chunk_records // 32, RECORD_WORDS), U32,
        sharding=NamedSharding(mesh, P("shard")),
    )
    sel = jax.ShapeDtypeStruct(
        (1 << cut, 128, chunk_records // 128, 4), U32,
        sharding=NamedSharding(mesh, P("shard", "key")),
    )
    _compile(mapped, db, sel)
