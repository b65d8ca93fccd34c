"""Pallas TPU kernel for the packed-XOR database inner product — on the MXU.

The XOR inner product (for each query, XOR of all database records whose
selection bit is 1 — `pir/internal/inner_product_hwy.cc:157-258`) is a
GF(2) matrix product: output bit j of word w is the *parity* of
``sum_r sel[q, r] * db_bit_j[r, w]``. That sum is an ordinary integer
matmul — exactly what the MXU does — so instead of VPU-style mask-and-XOR
(memory-layout hostile on TPU: measured 6 GB/s), the kernel computes
per-bit-plane bf16 matmuls with exact f32 accumulation (counts <= number
of records <= 2^24, all integers exact in f32) and takes parities at the
end.

Both operands stay **packed** in HBM; no `[nq, R]` mask is ever
materialized there:

* selections: ``uint32[nq, G]``, bit b of word g selects record 32g+b;
* database: staged once into bit-major order ``db_perm[b, g, w] =
  db_words[32g + b, w]`` (shape ``[32, G, W]``), so the kernel's
  fori-loop over the 32 bit-classes b only ever indexes the *leading*
  axis dynamically — the record class's selection bits fall out of the
  packed words as ``(words >> b) & 1`` with no lane reshuffle (Mosaic
  cannot lower minor-dim reshapes/repeats, which sank the VPU designs).

Grid: (query tiles, record-group tiles), record axis innermost; the f32
``[TQ, 32, W]`` count accumulator lives in VMEM across record tiles (the
revisiting-output pattern). Per step and bit-class, the DB tile's 32
value-bit-planes are peeled in VMEM (`(dbb >> j) & 1`) and hit the MXU as
``[TQ, TG] x [TG, W]`` bf16 dots. One database pass serves the whole
query batch.

Exactness bound: counts accumulate in f32, so the kernel requires
R <= 2^24 records (far above the 2^22 headline config); the v2 kernel
(`xor_inner_product_pallas2_staged`, int8 with exact int32 counts, the
one TPU serving uses) has no such cap.

Differentially tested against the jnp implementation and the numpy/native
oracles (tests/test_pallas.py); AOT-compiled for v5e at the serving shape
in tests/test_chip_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.profiling import scoped

U32 = jnp.uint32
I32 = jnp.int32

I8 = jnp.int8
BF16 = jnp.bfloat16
F32 = jnp.float32

# Record-group tile (32 records per group): 128 groups = 4096 records per
# grid step; the packed-selection block's lane dim is then 128, the TPU
# lane width.
_TILE_GROUPS = 128
# f32 holds integers exactly up to 2^24 — the parity trick's hard cap.
MAX_RECORDS_EXACT = 1 << 24


def permute_db_bitmajor(db_words: jnp.ndarray) -> jnp.ndarray:
    """uint32[R, W] row-major -> uint32[32, G, W] bit-major.

    Row 32g+b lands at [b, g, :]: all records selected by bit b of a
    packed selection word are contiguous along axis 1. The record count
    is zero-padded to a multiple of 32*_TILE_GROUPS (= 4096) so the group
    axis always tiles evenly at the full 128-lane width (zero rows never
    contribute to a XOR). One XLA pad+transpose, done once when the
    database is staged.
    """
    num_records, num_words = db_words.shape
    chunk = 32 * _TILE_GROUPS
    padded = ((num_records + chunk - 1) // chunk) * chunk
    if padded != num_records:
        db_words = jnp.pad(db_words, ((0, padded - num_records), (0, 0)))
    return jnp.transpose(
        db_words.reshape(padded // 32, 32, num_words), (1, 0, 2)
    )


def _ip_kernel(sel_ref, db_ref, out_ref, *, num_value_bits: int):
    """sel_ref: uint32[TQ, TG] packed; db_ref: uint32[32, TG, W] bit-major;
    out_ref: float32[TQ, 32, W] per-value-bit selection counts."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    def body(b, carry):
        # Selection bits of record class b (records 32g+b), ready for the
        # MXU: [TQ, TG] bf16 of 0/1.
        # Mosaic has no direct u32->bf16 cast; hop via i32 -> f32 (values
        # are 0/1, so every step is exact).
        sel_b = (
            ((sel_ref[:] >> b.astype(U32)) & U32(1))
            .astype(jnp.int32)
            .astype(F32)
            .astype(BF16)
        )
        dbb = db_ref[b]  # [TG, W] u32 — dynamic index on the leading axis
        for j in range(num_value_bits):
            bits_j = (
                ((dbb >> U32(j)) & U32(1))
                .astype(jnp.int32)
                .astype(F32)
                .astype(BF16)
            )  # [TG, W]
            out_ref[:, j, :] += lax.dot_general(
                sel_b,
                bits_j,
                (((1,), (0,)), ((), ())),
                preferred_element_type=F32,
            )
        return carry

    lax.fori_loop(0, 32, body, 0)


def _pick_group_tile(
    num_groups: int, max_tile: int = _TILE_GROUPS, lane_step: int = 8
) -> int:
    """Largest tile <= max_tile that divides num_groups and is a
    multiple of `lane_step`, or the full axis for small databases.

    `lane_step` is 128 when the group axis is a block's *last* (lane)
    dimension — Mosaic requires last block dims to be 128-divisible or
    span the whole array axis — and 8 (sublane) otherwise.
    `permute_db_bitmajor` pads so num_groups % _TILE_GROUPS == 0; the
    search only matters for hand-built layouts. A large layout with no
    legal tile is rejected rather than compiled as one giant VMEM block.
    """
    tg = min(max_tile, num_groups)
    tg -= tg % lane_step
    while tg >= lane_step:
        if num_groups % tg == 0:
            return tg
        tg -= lane_step
    # No legal tile at or under the request: round UP to the smallest
    # legal one (a sub-lane_step request like the old tile_groups=32
    # default would otherwise be Mosaic-rejected on hardware).
    if lane_step < num_groups and num_groups % lane_step == 0:
        return lane_step
    if num_groups > max(max_tile, 4 * lane_step):
        raise ValueError(
            f"no legal group tile for {num_groups} groups; stage the "
            "database with permute_db_bitmajor (which pads)"
        )
    return num_groups


def _stage_selections(selections: jnp.ndarray, num_groups: int):
    """Flatten packed selection blocks to [nq_pad, num_groups] words.

    Extra words beyond the staged layout's (zero-padded) groups are
    dropped; missing words and the query count's non-multiple-of-8 tail
    are zero-padded (zero selection bits never contribute to a XOR).
    Returns (packed, nq) with nq the caller's true query count.
    """
    nq = selections.shape[0]
    packed = selections.reshape(nq, -1)
    if packed.shape[1] > num_groups:
        packed = packed[:, :num_groups]
    elif packed.shape[1] < num_groups:
        packed = jnp.pad(packed, ((0, 0), (0, num_groups - packed.shape[1])))
    nq_pad = ((nq + 7) // 8) * 8
    if nq_pad != nq:
        packed = jnp.pad(packed, ((0, nq_pad - nq), (0, 0)))
    return packed, nq


@functools.partial(
    jax.jit, static_argnames=("tile_queries", "interpret")
)
def _ip_pallas_staged(
    db_perm: jnp.ndarray,
    packed: jnp.ndarray,
    tile_queries: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    _, num_groups, num_words = db_perm.shape
    nq = packed.shape[0]
    tg = _pick_group_tile(num_groups, lane_step=8 if interpret else 128)
    # Query tile: a multiple of 8 (TPU sublane) dividing the padded batch
    # (callers pad nq to a multiple of 8), or the whole batch if smaller.
    tq = min(tile_queries, nq)
    while tq > 8 and (nq % tq != 0 or tq % 8 != 0):
        tq -= 8 if tq % 8 == 0 else tq % 8
    if nq % tq != 0:
        tq = nq

    counts = pl.pallas_call(
        functools.partial(_ip_kernel, num_value_bits=32),
        grid=(nq // tq, num_groups // tg),
        in_specs=[
            pl.BlockSpec((tq, tg), lambda q, r: (q, r)),
            pl.BlockSpec((32, tg, num_words), lambda q, r: (0, r, 0)),
        ],
        out_specs=pl.BlockSpec((tq, 32, num_words), lambda q, r: (q, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, 32, num_words), F32),
        interpret=interpret,
    )(packed, db_perm)
    # Parity of each count is the output bit; recombine the 32 bit-planes.
    parity = counts.astype(jnp.int32).astype(U32) & U32(1)
    return (parity << jnp.arange(32, dtype=U32)[None, :, None]).sum(
        axis=1, dtype=U32
    )


def xor_inner_product_pallas_staged(
    db_perm: jnp.ndarray,
    selections: jnp.ndarray,
    tile_queries: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Serving-path entry: bit-major staged database, packed selections.

    db_perm: uint32[32, G, W] from `permute_db_bitmajor` (R = 32*G
    records, R % 128 == 0); selections: uint32[nq, B, 4] packed blocks
    with 128*B >= R. Returns uint32[nq, W].
    """
    _, num_groups, _ = db_perm.shape
    num_records = 32 * num_groups
    if num_records > MAX_RECORDS_EXACT:
        raise ValueError(
            f"pallas inner product supports at most {MAX_RECORDS_EXACT} "
            f"records (f32-exact parity counts); got {num_records}"
        )
    packed, nq = _stage_selections(selections, num_groups)
    nq_pad = packed.shape[0]
    out = _ip_pallas_staged(
        db_perm, packed, tile_queries=tile_queries, interpret=interpret
    )
    return out[:nq] if nq_pad != nq else out


def _ip_kernel_v2(sel_ref, db_ref, out_ref, *, j_chunk: int, int8: bool):
    """One large MXU dot per (grid step, value-bit chunk).

    v1 (`_ip_kernel`) issues 32x32 = 1024 tiny [TQ, TG] x [TG, W] dots per
    grid step; MXU pipeline fill dominates (measured 13.6 ms at the
    2^20 x 256B headline, ~10% MXU). Here the whole record tile is
    unpacked in VMEM into one [TQ, 32*TG] x [32*TG, j_chunk*W] dot per
    value-bit chunk: K grows 32x, the dot count per step drops from 1024
    to 32/j_chunk.

    Record order along K is b-major (k = b*TG + g, record 32g+b): the LHS
    tiles the packed selection words 32x along lanes (`pltpu.repeat`) and
    shifts by k//TG, the RHS is just `db_ref` flattened (major-dim merge
    [32, TG, W] -> [32TG, W] — no lane reshuffle, which Mosaic cannot
    lower). Value bits unpack the same way: repeat along lanes, shift by
    lane//W, so RHS column j*W + w matches the caller's [nq, 32, W]
    recombination.

    int8=True uses the int8 MXU path (i8 x i8 -> i32 dot): 2x the bf16
    rate and exact int32 counts — no f32 2^24-record exactness cap.

    sel_ref: uint32[TQ, TG]; db_ref: uint32[32, TG, W];
    out_ref: float32|int32[TQ, 32*W] counts.
    """

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    tq, tg = sel_ref.shape
    _, _, w = db_ref.shape
    tr = 32 * tg

    def to_mm(bits_u32):
        # Mosaic has no direct u32->bf16 cast; hop via i32 (exact 0/1s).
        as_i32 = bits_u32.astype(I32)
        return as_i32.astype(I8) if int8 else as_i32.astype(F32).astype(BF16)

    sel_rep = pltpu.repeat(sel_ref[:], 32, axis=1)  # [TQ, 32*TG] tiled
    b_iota = lax.broadcasted_iota(U32, (tq, tr), 1) // U32(tg)
    lhs = to_mm((sel_rep >> b_iota) & U32(1))

    dbw = db_ref[:].reshape(tr, w)  # b-major record rows
    # j_chunk=1 repeats by factor 1 — expected to lower as an identity,
    # sidestepping Mosaic's narrow-source repeat miscompile (the entry
    # point drops to 1 for W<16 records; whether a factor-1 repeat on a
    # narrow source is really legal is UNPROBED on hardware —
    # benchmarks/kernel_smoke.py's W=8 case answers it; a crash raises
    # out of serving). The repeat
    # also launders shard_map's varying-axes metadata exactly like the
    # multi-factor path: a direct ref read would carry the mesh axis and
    # mismatch the unvarying iotas and constants throughout the kernel
    # (the VMA checker runs at trace time on any backend; the declared
    # out_shape vma covers the result).
    db_rep = pltpu.repeat(dbw, j_chunk, axis=1)
    acc_t = I32 if int8 else F32
    for jc in range(0, 32, j_chunk):
        if j_chunk == 1:
            # Narrow records: shift by the chunk's constant bit index.
            rhs = to_mm((db_rep >> U32(jc)) & U32(1))
            out_ref[:, jc * w : (jc + 1) * w] += lax.dot_general(
                lhs, rhs, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_t,
            )
            continue
        j_iota = (
            lax.broadcasted_iota(U32, (tr, j_chunk * w), 1) // U32(w)
        ) + U32(jc)
        rhs = to_mm((db_rep >> j_iota) & U32(1))
        out_ref[:, jc * w : (jc + j_chunk) * w] += lax.dot_general(
            lhs, rhs, (((1,), (0,)), ((), ())), preferred_element_type=acc_t
        )


@functools.partial(
    jax.jit,
    static_argnames=("tile_queries", "tile_groups", "j_chunk", "int8",
                     "interpret", "vma"),
)
@scoped("pir.inner_product")
def _ip_pallas_staged_v2(
    db_perm: jnp.ndarray,
    packed: jnp.ndarray,
    tile_queries: int = 64,
    tile_groups: int = 128,
    j_chunk: int = 8,
    int8: bool = False,
    interpret: bool = False,
    vma: tuple = (),
) -> jnp.ndarray:
    _, num_groups, num_words = db_perm.shape
    nq = packed.shape[0]
    tg = _pick_group_tile(
        num_groups, max_tile=tile_groups,
        # Mosaic requires the selections block's lane dim (groups) to be
        # 128-divisible or span the axis; interpret mode has no such rule
        # (and the tile-variant tests exercise smaller tiles there).
        lane_step=8 if interpret else 128,
    )
    # Cap the query tile so the i32/f32 counts block stays ~<=2 MB in
    # VMEM (tq * 32W * 4 B): wide records would otherwise blow the
    # budget at large tiles (e.g. W=256 caps tq at 64).
    tq_cap = max(8, (2 << 20) // (32 * num_words * 4) // 8 * 8)
    tq = min(tile_queries, nq, tq_cap)
    while tq > 8 and (nq % tq != 0 or tq % 8 != 0):
        tq -= 8 if tq % 8 == 0 else tq % 8
    if nq % tq != 0:
        tq = nq

    acc_t = I32 if int8 else F32
    counts = pl.pallas_call(
        functools.partial(
            _ip_kernel_v2, j_chunk=j_chunk, int8=int8,
        ),
        grid=(nq // tq, num_groups // tg),
        in_specs=[
            pl.BlockSpec((tq, tg), lambda q, r: (q, r)),
            pl.BlockSpec((32, tg, num_words), lambda q, r: (0, r, 0)),
        ],
        out_specs=pl.BlockSpec(
            (tq, 32 * num_words), lambda q, r: (q, 0)
        ),
        # vma: required when called inside a shard_map with the sharding
        # checker on (the multi-chip MXU step, `parallel/sharded.py`).
        out_shape=jax.ShapeDtypeStruct(
            (nq, 32 * num_words), acc_t,
            **({"vma": frozenset(vma)} if vma else {}),
        ),
        interpret=interpret,
    )(packed, db_perm)
    parity = counts.reshape(nq, 32, num_words).astype(I32).astype(U32) & U32(1)
    return (parity << jnp.arange(32, dtype=U32)[None, :, None]).sum(
        axis=1, dtype=U32
    )


def xor_inner_product_pallas2_staged(
    db_perm: jnp.ndarray,
    selections: jnp.ndarray,
    tile_queries: int = 256,
    tile_groups: int = 128,
    j_chunk: int = 32,
    int8: bool = True,
    interpret: bool = False,
    vma: tuple = (),
) -> jnp.ndarray:
    """v2 serving entry: same staged layout/signature as
    `xor_inner_product_pallas_staged`, one large dot per step.

    With int8=True the parity counts accumulate exactly in int32, so the
    record cap is the int32 range rather than f32's 2^24. The query tile
    defaults high (256) because the in-VMEM database-tile unpack repeats
    per query tile: large batches (dense_big's 1024 queries) pay it
    nq/tile_queries times.
    """
    _, num_groups, num_words = db_perm.shape
    num_records = 32 * num_groups
    if not int8 and num_records > MAX_RECORDS_EXACT:
        raise ValueError(
            f"bf16/f32 parity counts support at most {MAX_RECORDS_EXACT} "
            f"records; got {num_records} (use int8=True)"
        )
    if 32 % j_chunk != 0:
        raise ValueError(f"j_chunk must divide 32; got {j_chunk}")
    # In this kernel's 2-D axis-1 db repeat, Mosaic's `pltpu.repeat`
    # miscompiles (tpu_compile_helper exit 1) when
    # the source lane dim is below a half lane-tile and the factor exceeds
    # 8 — mapped on v5e 2026-07-31: W∈{4,8} × j_chunk∈{16,32} all crash,
    # W≥16 all legal. The 2026-07-31 kernel smoke then crashed at
    # W=8 x j_chunk=8 too (tpu_compile_helper exit 1), so the true
    # boundary is the SOURCE width, not the factor: for W<16 skip the
    # in-kernel db repeat entirely (j_chunk=1 needs no repeat). j_chunk
    # only affects throughput, so degrade loudly instead of crashing —
    # an A/B over j_chunk values must not silently time identical runs.
    if num_words < 16 and j_chunk > 1:
        if j_chunk != 32:  # 32 is the default, not an explicit request
            import warnings

            warnings.warn(
                f"narrow records ({num_words} words): j_chunk={j_chunk} "
                "dropped to 1 to dodge Mosaic's narrow-source repeat "
                "miscompile",
                stacklevel=2,
            )
        j_chunk = 1
    # The kernel's selections repeat has a fixed factor of 32, so a group
    # tile under 16 lanes hits the same miscompile with no knob to cap.
    # `permute_db_bitmajor` pads serving layouts to 128-group multiples;
    # only hand-built layouts can get here.
    if not interpret and num_groups < 16:
        raise ValueError(
            f"compiled v2 kernel needs >= 16 selection groups (512 "
            f"records); got {num_groups} — pad the staged layout or use "
            f"xor_inner_product_pallas_staged"
        )
    packed, nq = _stage_selections(selections, num_groups)
    nq_pad = packed.shape[0]
    out = _ip_pallas_staged_v2(
        db_perm,
        packed,
        tile_queries=tile_queries,
        tile_groups=tile_groups,
        j_chunk=j_chunk,
        int8=int8,
        interpret=interpret,
        vma=vma,
    )
    return out[:nq] if nq_pad != nq else out


def xor_inner_product_pallas2_accumulate(
    acc: jnp.ndarray,
    db_perm_span: jnp.ndarray,
    selections: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Partial-accumulate entry for the streaming serving scan: XOR one
    staged block span's MXU inner product into per-query accumulators.

    acc: uint32[nq, W]; db_perm_span: uint32[32, Gc, W] one bit-major
    staged span (`stage_db_chunks_bitmajor`); selections: uint32[nq, B, 4]
    covering exactly that span (`_stage_selections` pads the group axis to
    Gc). Extra kwargs pass through to `xor_inner_product_pallas2_staged`.
    """
    return acc ^ xor_inner_product_pallas2_staged(
        db_perm_span, selections, **kwargs
    )


def stage_db_chunks_bitmajor(
    db_words: jnp.ndarray, num_chunks: int
) -> jnp.ndarray:
    """Split a (permuted) row-major database into equal record spans and
    bit-major stage each: uint32[R, W] -> uint32[num_chunks, 32, Gc, W].

    Each chunk is independently padded to a 4096-record multiple by
    `permute_db_bitmajor`, so Gc >= 128 always satisfies the compiled v2
    kernel's 16-group floor regardless of chunk size.
    """
    num_records, num_words = db_words.shape
    if num_chunks <= 0 or num_records % num_chunks:
        raise ValueError(
            f"record count {num_records} is not divisible into "
            f"{num_chunks} chunks"
        )
    chunk_records = num_records // num_chunks
    return jax.vmap(permute_db_bitmajor)(
        db_words.reshape(num_chunks, chunk_records, num_words)
    )


def xor_inner_product_pallas(
    db_words: jnp.ndarray,
    selections: jnp.ndarray,
    tile_queries: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Convenience entry from a row-major database (permutes per call;
    the serving path stages `permute_db_bitmajor` once instead).

    db_words: uint32[R, W], R a multiple of 128; selections:
    uint32[nq, B, 4]. Returns uint32[nq, W].
    """
    num_records, _ = db_words.shape
    if num_records % 128 != 0:
        raise ValueError("record count must be padded to a multiple of 128")
    return xor_inner_product_pallas_staged(
        permute_db_bitmajor(db_words),
        selections,
        tile_queries=tile_queries,
        interpret=interpret,
    )
