"""Pallas TPU kernel for TopK's compaction: the kept entries of a slice,
in ascending index order, without a sort, scatter or gather of length n.

The keep rule comes from outside (``ref.topk_threshold``): entry i is
kept when its magnitude key is above ``t``, or equal to ``t`` with
i < ``c``. That is exactly ``lax.top_k``'s set, ties to the lower index.

Each grid step streams one [rows, 128] block of the slice and
 1. builds the keep mask and, per entry, d = the number of dropped
    entries before it in the block (row-major). The lane part is one
    matmul of the drop mask against a strict upper-triangular 0/1
    matrix, the row part one matmul of the row totals against a strict
    lower-triangular one: 0/1 and small integers, exact in bf16 with f32
    accumulation;
 2. moves every kept entry left by its d with a shift network: for bit
    b, the entries whose d has bit b set move 2^b places (a lane roll
    with a carry into the next row for 2^b < 128, a sublane roll above).
    Kept entries keep their order and never land on each other: d is
    non-decreasing along the kept entries, so after the bits below b
    two of them at i < j sit at p_j - p_i >= 1 + (d_j mod 2^b) -
    (d_i mod 2^b) > 0 places apart. An entry whose d has bit b set sits
    at least 2^b from the block's start, so the cyclic rolls never wrap
    a moving entry;
 3. shifts the compacted block right by the running count's offset in
    its 1024-entry chunk, puts the previous block's part-filled chunk in
    front of it, and copies the whole buffer to the output at that
    chunk's row with one DMA. The buffer's tail beyond the kept entries
    is overwritten by the next block's DMA, which therefore starts only
    once the previous one has finished (two buffers alternate, so the
    wait overlaps a block of compute).

The grid is (slice, block), sequential; the running count lives in SMEM.
Values leave the kernel as f32 (exact for bf16 and f32 inputs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import magnitude_key32

LANES = 128
CHUNK_ROWS = 8                       # one output chunk: 8 rows = 1024 entries
CHUNK = CHUNK_ROWS * LANES
MAX_BLOCK_ROWS = 512


def _flat_shift_left(x, s: int, lane):
    """result[p] = x[(p + s) mod size] over the row-major flattening."""
    rows = x.shape[0]
    if s % LANES == 0:
        return pltpu.roll(x, rows - s // LANES, 0)
    y = pltpu.roll(x, LANES - s, 1)          # y[r, c] = x[r, (c + s) % 128]
    return jnp.where(lane < LANES - s, y, pltpu.roll(y, rows - 1, 0))


def _flat_shift_right(x, s, lane):
    """result[p] = x[(p - s) mod size] for a traced 0 <= s < CHUNK."""
    rows = x.shape[0]
    sl, sr = s % LANES, s // LANES
    y = pltpu.roll(x, sl, 1)                 # y[r, c] = x[r, (c - sl) % 128]
    return jnp.where(lane >= sl, pltpu.roll(y, sr, 0),
                     pltpu.roll(y, (sr + 1) % rows, 0))


def _topk_select_kernel(t_ref, c_ref, x_ref, idx_hbm, val_hbm,
                        buf_i, buf_v, stage_i, stage_v, off_ref, sem, *,
                        block_rows: int, max_row0: int):
    b, i = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    g = b * nb + i                           # global step: DMA order
    slot = g % 2
    rows = block_rows
    size = rows * LANES

    @pl.when(i == 0)
    def _reset():
        off_ref[0] = 0
        stage_i[...] = jnp.zeros_like(stage_i)
        stage_v[...] = jnp.zeros_like(stage_v)

    # 1. keep mask and the dropped-before count d of every entry
    a = x_ref[0].astype(jnp.float32)
    key = magnitude_key32(a)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    local = row * LANES + lane
    t, c = t_ref[b], c_ref[b]
    keep = (key > t) | ((key == t) & (i * size + local < c))
    drop = jnp.where(keep, 0.0, 1.0).astype(jnp.bfloat16)
    li = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = jnp.where(li < lj, 1.0, 0.0).astype(jnp.bfloat16)
    lane_excl = jnp.dot(drop, upper, preferred_element_type=jnp.float32)
    row_tot = jnp.sum(drop.astype(jnp.float32), axis=1, keepdims=True)
    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    rj = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    lower = jnp.where(rj < ri, 1.0, 0.0).astype(jnp.bfloat16)
    row_excl = jnp.dot(lower, jnp.broadcast_to(row_tot, (rows, LANES))
                       .astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    d = jnp.where(keep, (lane_excl + row_excl).astype(jnp.int32), -1)
    cnt = size - jnp.sum(row_tot).astype(jnp.int32)

    # 2. shift network: move each kept entry left by d
    v = a
    for bit in range((size - 1).bit_length()):
        s = 1 << bit
        d_in = _flat_shift_left(d, s, lane)
        v_in = _flat_shift_left(v, s, lane)
        arrive = (d_in >= 0) & (((d_in >> bit) & 1) == 1)
        stay = (d >= 0) & (((d >> bit) & 1) == 0)
        v = jnp.where(arrive, v_in, v)
        d = jnp.where(arrive, d_in, jnp.where(stay, d, -1))
    gidx = jnp.where(d >= 0, i * size + local + d, 0)

    # 3. place behind the part-filled chunk and copy out
    off = off_ref[0]
    s = off % CHUNK
    row0 = jnp.minimum((off // CHUNK) * CHUNK_ROWS, max_row0)
    pad_i = jnp.zeros((CHUNK_ROWS, LANES), jnp.int32)
    ext_lane = jax.lax.broadcasted_iota(
        jnp.int32, (rows + CHUNK_ROWS, LANES), 1)
    placed_i = _flat_shift_right(
        jnp.concatenate([gidx, pad_i], 0), s, ext_lane)
    placed_v = _flat_shift_right(
        jnp.concatenate([v, pad_i.astype(jnp.float32)], 0), s, ext_lane)
    head = local[:CHUNK_ROWS] < s
    buf_i[slot] = placed_i
    buf_v[slot] = placed_v
    buf_i[slot, :CHUNK_ROWS] = jnp.where(head, stage_i[...],
                                         placed_i[:CHUNK_ROWS])
    buf_v[slot, :CHUNK_ROWS] = jnp.where(head, stage_v[...],
                                         placed_v[:CHUNK_ROWS])
    end = s + cnt
    tail = pl.multiple_of((end // CHUNK) * CHUNK_ROWS, CHUNK_ROWS)
    stage_i[...] = buf_i[slot, pl.ds(tail, CHUNK_ROWS)]
    stage_v[...] = buf_v[slot, pl.ds(tail, CHUNK_ROWS)]
    off_ref[0] = off + cnt

    def copies(sl, b_, r0):
        n = rows + CHUNK_ROWS
        return (pltpu.make_async_copy(buf_i.at[sl],
                                      idx_hbm.at[b_, pl.ds(r0, n)],
                                      sem.at[0, sl]),
                pltpu.make_async_copy(buf_v.at[sl],
                                      val_hbm.at[b_, pl.ds(r0, n)],
                                      sem.at[1, sl]))

    @pl.when(g > 0)
    def _wait_previous():
        for cp in copies(1 - slot, 0, 0):
            cp.wait()

    for cp in copies(slot, b, row0):
        cp.start()

    @pl.when(g == pl.num_programs(0) * nb - 1)
    def _wait_last():
        for cp in copies(slot, b, row0):
            cp.wait()


def block_rows_for(n: int) -> int:
    """Rows of one grid step: the slice's rows rounded to the bf16 tile
    (16), at most ``MAX_BLOCK_ROWS``."""
    rows = -(-n // LANES)
    return min(MAX_BLOCK_ROWS, -(-rows // 16) * 16)


def topk_select(x: jax.Array, t: jax.Array, c: jax.Array, k: int, *,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Kept entries of every slice of ``x`` [..., n] (bf16 or f32) under
    the rule (``t``, ``c``, each [...]) of ``ref.topk_threshold``, which
    must keep exactly ``k`` of each slice: (indices int32 [..., k]
    ascending, values f32 [..., k]). ``t`` is a ``magnitude_key32``."""
    lead, n = x.shape[:-1], x.shape[-1]
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        raise TypeError(f"topk_select takes bf16 or f32, not {x.dtype}")
    block_rows = block_rows_for(n)
    size = block_rows * LANES
    nblocks = -(-n // size)
    pad = [(0, 0)] * len(lead) + [(0, nblocks * size - n)]
    # the slice axis is merged on the [rows, 128] view, which keeps the
    # tiled minor dims as they are (no relayout of the input)
    x3 = jnp.pad(x, pad).reshape(lead + (nblocks * block_rows, LANES))
    x3 = x3.reshape((-1,) + x3.shape[-2:])
    bsz = x3.shape[0]
    out_rows = -(-k // CHUNK) * CHUNK_ROWS + block_rows + CHUNK_ROWS
    kernel = functools.partial(
        _topk_select_kernel, block_rows=block_rows,
        max_row0=out_rows - block_rows - CHUNK_ROWS)
    buf = (2, block_rows + CHUNK_ROWS, LANES)
    idx, val = pl.pallas_call(
        kernel,
        name="topk_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, nblocks),
            in_specs=[pl.BlockSpec((1, block_rows, LANES),
                                   lambda b, i, t_, c_: (b, i, 0))],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[
                pltpu.VMEM(buf, jnp.int32),
                pltpu.VMEM(buf, jnp.float32),
                pltpu.VMEM((CHUNK_ROWS, LANES), jnp.int32),
                pltpu.VMEM((CHUNK_ROWS, LANES), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=(jax.ShapeDtypeStruct((bsz, out_rows, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((bsz, out_rows, LANES),
                                        jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(t.reshape(-1).astype(jnp.int32), c.reshape(-1).astype(jnp.int32), x3)
    out = lead + (k,)
    # the barrier keeps XLA from folding this slice into a consumer's
    # pad as a select against a constant mask of k entries, held in
    # device memory for the life of the program
    return jax.lax.optimization_barrier(
        (idx.reshape(bsz, -1)[:, :k].reshape(out),
         val.reshape(bsz, -1)[:, :k].reshape(out)))
