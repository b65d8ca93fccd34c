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


@pytest.mark.parametrize("party", [0, 1])
def test_full_domain_direct_exit_compiles(one_chip, party):
    """The full-domain cell's exit and leaf stage at log domain 20,
    uint64 (2^19 leaves of 32-block words at 2^14 positions): no gather
    moves fewer than one 32-leaf row, and the output leaves the program
    in the layout `{0,1}` the API returns it in (no relayout after)."""
    from distributed_point_functions_tpu import dpf as dpf_mod

    positions = 1 << 14
    order = dpf_mod._bitrev_order(14)

    def full_domain(state, ctrl, vc):
        limbs, c = dpf_mod._direct_exit(state, ctrl, order, 32)
        return dpf_mod._leaf_stage_lanes(limbs, c, vc, bits=64, cepb=2,
                                         party=party)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, U32, sharding=one_chip)

    text = jax.jit(full_domain).lower(
        shape(16, 8, positions), shape(positions), shape(2, 2)
    ).compile().as_text()
    layout = text.split("entry_computation_layout={")[1].split("\n")[0]
    assert "->u32[1048576,2]{0,1:T(2,128)}" in layout
    for line in text.splitlines():
        if " gather(" in line:
            slices = line.split("slice_sizes={")[1].split("}")[0]
            assert np.prod([int(s) for s in slices.split(",")]) >= 32 * 4


def test_hierarchical_rows_exit_compiles(one_chip):
    """A resumed hierarchy level on the rows exit: the kernel program
    (chunk-major planes, any level count and frontier up to its chunks)
    holds one instance of each kernel, and the exit reads the selected
    leaves with one gather of whole 8-word rows (the hashed and the
    pre-hash block side by side), returning the outputs
    in the `{0,1}` layout of the API's uint32[n, 1]. Chunks of 256
    lanes (a frontier of 2^13 nodes), as the kernel cases above stop
    there; the cell's 1024-lane chunks compile the same program."""
    from distributed_point_functions_tpu import dpf as dpf_mod

    nodes = 1 << 13
    lanes, live, chunks = dpf_mod._chunk_shape(nodes, 2)
    assert (lanes, live, chunks) == (256, 1, dpf_mod._MIN_CHUNKS)

    def shape(*dims, dtype=U32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    state, ctrl = shape(chunks, 16, 8, lanes), shape(chunks, lanes)
    levels = dpf_mod._CHUNK_LEVELS
    kernels = _compile(
        dpf_mod._chunk_kernels_fn(True), state, ctrl,
        shape(dtype=jnp.int32), shape(dtype=jnp.int32),
        shape(levels, 16, 8, 1), shape(levels), shape(levels),
    ).as_text()
    assert kernels.count('custom_call_target="tpu_custom_call"') == 2

    sel = shape(nodes, dtype=jnp.int32)
    text = jax.jit(dpf_mod._rows_exit_fn(True, 32, 4, 4)).lower(
        state, ctrl, state, shape(4, 1), shape(), sel, sel
    ).compile().as_text()
    layout = text.split("entry_computation_layout={")[1].split("\n")[0]
    assert f"->(u32[{nodes * 4},1]{{0,1:T(1,128)}}" in layout
    rows = [line for line in text.splitlines()
            if " gather(" in line and "slice_sizes={1,8,1,1}" in line]
    assert len(rows) == 1
