"""Pallas TPU kernel: blockwise magnitude Top-K sparsification.

This is the TPU adaptation of FusionLLM §6's CUDA Top-K library ("faster
than PyTorch TopK").  A GPU kernel would partial-sort per thread block and
emit (values, indices); TPUs have no efficient scatter and the VPU hates
data-dependent permutation, so we rethink the algorithm (DESIGN.md §2):

* the tensor is tiled into VMEM blocks; each block selects its own top
  ``k`` — embarrassingly parallel over the grid, no cross-block traffic;
* the k-th largest magnitude is found *exactly* by a 31-step binary search
  over IEEE-754 bit patterns (for non-negative floats the int32 bit pattern
  is order-isomorphic to the value), every step being a dense
  compare+reduce — pure VPU work, no sort;
* the output stays **dense** (values below threshold zeroed).  The sparse
  wire encoding (mask bitmap + packed values) is a layout decision for the
  transport layer; on-chip we keep dense tiles so downstream matmuls feed
  the MXU directly.

``ef_topk`` fuses error-feedback (compress x+residual, emit new residual)
around the same threshold search — one extra VMEM-resident add/sub, no
extra HBM round-trip.

``encode_topk`` / ``ef_encode_topk`` / ``decode_topk`` are the fused *wire*
kernels: threshold search + mask-bitmap emission + packed-value compaction
in one pallas_call (the "mask" encoding `wire_bytes` prices).  Unlike the
dense kernels they are tie-capped — the wire has exactly k slots per block,
so among threshold ties the first ``k - n_above`` in index order win.  The
packed values fill whole word columns inside the kernel (``ceil(k/32)``
of them); wrappers slice them back to k.

Layout.  Every grid step owns one block as a 2-D VMEM tile whose last two
dimensions are the whole block, so the (8, 128) tiling rule holds for any
block count.  The dense kernels view a block row-major as
``(block/128, 128)``.  The wire kernels view it *word-major* as
``(32, block/32)``: column ``w`` holds the 32 elements of bitmap word ``w``,
so packing a word is a reduction over sublanes and unpacking is a shift by
the sublane index.  Index order is then column-major, and the in-kernel
prefix count (tie rank, packed slot) is a log-step shift-and-add along
sublanes and then lanes (``pltpu.roll``).  Mosaic lowers no gather or
scatter, so packing the kept values is a log-step compaction network: each
kept element moves toward its slot by the set bits of its displacement,
one roll-and-select step per bit (``log2(block)`` steps whatever k is),
and decoding runs the same network in reverse.

Kernels are validated in interpret mode against :mod:`repro.kernels.ref`
(exact equality — same selection set by construction).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 4096        # elements per grid step (fits VMEM many times
                            # over; multiple of 8*128 VPU tiles)
_SEARCH_BITS = 31           # full int32 positive range
_LANE = 128                 # TPU lane width
_WORD = 32                  # bits per bitmap word


def _count(mask: jax.Array) -> jax.Array:
    """Number of true entries of a 2-D tile, as a (1, 1) int32."""
    return jnp.sum(mask.astype(jnp.int32), axis=(0, 1), keepdims=True)


def _kth_threshold_bits(mag_bits: jax.Array, k: int) -> jax.Array:
    """Largest t such that count(mag_bits >= t) >= k (t=0 if k >= n), as a
    (1, 1) int32.

    mag_bits: int32 bit patterns of non-negative floats (monotone in value).
    31 fixed iterations of compare+reduce — branch-free, VPU-only.
    """
    lo = jnp.zeros((1, 1), jnp.int32)
    hi = jnp.full((1, 1), 0x7F800000, jnp.int32)  # +inf bounds every
    # magnitude (also keeps hi - lo + 1 inside int32 — 2^31-1 would overflow)

    def body(_, carry):
        lo, hi = carry
        mid = lo + ((hi - lo + 1) >> 1)
        take = _count(mag_bits >= mid) >= k
        return (jnp.where(take, mid, lo), jnp.where(take, hi, mid - 1))

    lo, _ = jax.lax.fori_loop(0, _SEARCH_BITS, body, (lo, hi))
    return lo


def _mag_bits(x32: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(jnp.abs(x32), jnp.int32)


def _round_to(x32: jax.Array, dtype) -> jax.Array:
    """Round an f32 value to the storage dtype and back: what ``x + r``
    computed in ``dtype`` holds, so the selection matches the oracle's
    eagerly-rounded value bit for bit."""
    return x32.astype(dtype).astype(jnp.float32)


def _topk_block_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)
    bits = _mag_bits(x)
    keep = bits >= _kth_threshold_bits(bits, k)
    o_ref[...] = jnp.where(keep, x, 0.0).astype(o_ref.dtype)


def _ef_topk_block_kernel(x_ref, r_ref, sent_ref, newr_ref, *, k: int):
    corrected = _round_to(x_ref[...].astype(jnp.float32)
                          + r_ref[...].astype(jnp.float32), x_ref.dtype)
    bits = _mag_bits(corrected)
    keep = bits >= _kth_threshold_bits(bits, k)
    sent = jnp.where(keep, corrected, 0.0)
    sent_ref[...] = sent.astype(sent_ref.dtype)
    newr_ref[...] = (corrected - sent).astype(newr_ref.dtype)


def _prep(x: jax.Array, block: int) -> Tuple[jax.Array, int, Tuple[int, ...]]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    nb = -(-n // block)
    flat = jnp.pad(flat, (0, nb * block - n)).reshape(nb, block)
    return flat, n, x.shape


def _row_tiles(tiles: jax.Array) -> jax.Array:
    """(nb, B) -> (nb, B/128, 128) row-major tiles (one row if B is not a
    multiple of 128 — interpret-mode test sizes)."""
    nb, block = tiles.shape
    lanes = _LANE if block % _LANE == 0 else block
    return tiles.reshape(nb, block // lanes, lanes)


def _out(shape, dtype, like: jax.Array) -> jax.ShapeDtypeStruct:
    """A pallas_call output that varies over the same mesh axes as its
    input ``like`` (so the kernels also run inside ``shard_map``)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _tile_spec(tile: Tuple[int, int]) -> pl.BlockSpec:
    """One block per grid step, the leading block axis squeezed away."""
    return pl.BlockSpec((None,) + tuple(tile), lambda i: (i, 0, 0))


def _grid_call(kernel, tiles: jax.Array, n_in: int, n_out: int, k: int,
               interpret: bool):
    nb = tiles.shape[0]
    shape = _out(tiles.shape, tiles.dtype, tiles)
    spec = _tile_spec(tiles.shape[1:])
    return pl.pallas_call(
        functools.partial(kernel, k=k),
        grid=(nb,),
        in_specs=[spec] * n_in,
        out_specs=[spec] * n_out if n_out > 1 else spec,
        out_shape=[shape] * n_out if n_out > 1 else shape,
        interpret=interpret,
    )


def blockwise_topk_mask(x: jax.Array, k_per_block: int,
                        block: int = DEFAULT_BLOCK,
                        interpret: bool = True) -> jax.Array:
    """Dense blockwise Top-K (Pallas).  interpret=True off-TPU; on a TPU
    pass interpret=False."""
    if x.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        raise TypeError(f"unsupported dtype {x.dtype}")
    k = int(min(max(k_per_block, 1), block))
    tiles, n, shape = _prep(x, block)
    tiles = _row_tiles(tiles)
    out = _grid_call(_topk_block_kernel, tiles, 1, 1, k, interpret)(tiles)
    return out.reshape(-1)[:n].reshape(shape)


def ef_topk(x: jax.Array, residual: jax.Array, k_per_block: int,
            block: int = DEFAULT_BLOCK,
            interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Fused error-feedback Top-K: (sent, new_residual)."""
    k = int(min(max(k_per_block, 1), block))
    tiles, n, shape = _prep(x, block)
    rtiles, _, _ = _prep(residual, block)
    tiles, rtiles = _row_tiles(tiles), _row_tiles(rtiles)
    fn = _grid_call(_ef_topk_block_kernel, tiles, 2, 2, k, interpret)
    sent, newr = fn(tiles, rtiles)
    return (sent.reshape(-1)[:n].reshape(shape),
            newr.reshape(-1)[:n].reshape(shape))


# ---------------------------------------------------------------------------
# Fused wire-encode / decode kernels
# ---------------------------------------------------------------------------

def _shift_in(v: jax.Array, d: int, axis: int) -> jax.Array:
    """``v`` moved ``d`` places toward higher indices along ``axis``, zeros
    filling the vacated low end."""
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    return jnp.where(idx >= d, pltpu.roll(v, d, axis), 0)


def _prefix_count(m: jax.Array) -> jax.Array:
    """Inclusive prefix sum of an int32 word-major (32, W) tile in index
    order (column-major): log-step shift-and-add down each column, then an
    exclusive scan of the column totals across lanes."""
    c = m
    d = 1
    while d < c.shape[0]:
        c = c + _shift_in(c, d, 0)
        d *= 2
    tot = c[-1:, :]
    e = tot
    d = 1
    while d < e.shape[1]:
        e = e + _shift_in(e, d, 1)
        d *= 2
    return c + (e - tot)


def _keep_capped_block(x32: jax.Array, k: int) -> jax.Array:
    """Tie-capped keep-mask for one word-major tile: exactly k kept.
    Everything strictly above the k-th largest bit pattern, plus the first
    ``k - n_above`` threshold ties in index order."""
    bits = _mag_bits(x32)
    thr = _kth_threshold_bits(bits, k)
    above = bits > thr
    tie = bits == thr
    tie_rank = _prefix_count(tie.astype(jnp.int32))
    return above | (tie & (tie_rank <= k - _count(above)))


def _linear_index(shape) -> jax.Array:
    """Index order of a word-major (32, W) tile: element (b, w) is w*32+b."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 1) * _WORD
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0))


def _move(a: jax.Array, s: int, up: bool) -> jax.Array:
    """Move every entry of a word-major tile ``s`` places along index order
    (toward higher indices if ``up``), wrapping around the tile.  ``s`` is
    a power of two: below 32 it crosses sublanes, carrying into the
    neighbouring column; from 32 on it is a whole-column roll."""
    S, W = a.shape
    if s % S == 0:
        m = s // S
        return pltpu.roll(a, m if up else W - m, 1)
    b = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    r0 = pltpu.roll(a, s if up else S - s, 0)
    if W == 1:
        return r0
    r1 = pltpu.roll(r0, 1 if up else W - 1, 1)
    return jnp.where(b >= s, r0, r1) if up else jnp.where(b < S - s, r0, r1)


def _route(vals: jax.Array, disp: jax.Array, valid: jax.Array, up: bool):
    """Move each valid entry ``disp`` places along index order — down
    (compaction) taking the bits of ``disp`` lowest first, or up
    (expansion) highest first, exactly undoing a compaction.  Down, the
    kept elements stay in strictly increasing positions after every step
    (their displacements never decrease along the index, and two of them
    are at least as far apart as their displacements differ), so no step
    lands one element on another.  Returns (vals, disp, valid) moved."""
    nbits = max(1, (vals.size - 1).bit_length())
    for t in (reversed(range(nbits)) if up else range(nbits)):
        s = 1 << t
        mover = valid & (((disp >> t) & 1) == 1)
        stay = valid & ~mover
        landed = _move(mover.astype(jnp.int32), s, up) == 1
        vals = jnp.where(landed, _move(vals, s, up),
                         jnp.where(stay, vals, jnp.zeros_like(vals)))
        disp = jnp.where(landed, _move(disp, s, up), disp)
        valid = stay | landed
    return vals, disp, valid


def _displacement(keep_i: jax.Array) -> jax.Array:
    """How far each kept element sits from its packed slot: its index minus
    the number of kept elements before it."""
    return _linear_index(keep_i.shape) - (_prefix_count(keep_i) - 1)


def _emit_encoded(x32: jax.Array, keep: jax.Array, v_ref, m_ref):
    """Write bitmap words (LSB-first, as int32 bit patterns) and the
    index-order packed values: the kept elements compacted to the front of
    the tile, whose first columns are the value slots."""
    keep_i = keep.astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, keep_i.shape, 0)
    m_ref[...] = jnp.sum(keep_i << shifts, axis=0, keepdims=True)
    packed, _, _ = _route(x32, _displacement(keep_i), keep, up=False)
    v_ref[...] = packed[:, :v_ref.shape[1]].astype(v_ref.dtype)


def _encode_block_kernel(x_ref, v_ref, m_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)
    _emit_encoded(x, _keep_capped_block(x, k), v_ref, m_ref)


def _ef_encode_block_kernel(x_ref, r_ref, v_ref, m_ref, newr_ref, *,
                            k: int):
    corrected = _round_to(x_ref[...].astype(jnp.float32)
                          + r_ref[...].astype(jnp.float32), x_ref.dtype)
    keep = _keep_capped_block(corrected, k)
    _emit_encoded(corrected, keep, v_ref, m_ref)
    newr_ref[...] = jnp.where(keep, 0.0, corrected).astype(newr_ref.dtype)


def _decode_block_kernel(v_ref, m_ref, o_ref, tile_ref):
    """Expand the packed values back to their indices: the displacements
    are compacted like the values were, then every value retraces its
    compaction in reverse."""
    keep_i = (m_ref[...] >> jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)) & 1
    disp = _displacement(keep_i)
    disp, _, slots = _route(disp, disp, keep_i == 1, up=False)
    tile_ref[...] = jnp.zeros(tile_ref.shape, jnp.float32)
    tile_ref[:, :v_ref.shape[1]] = v_ref[...].astype(jnp.float32)
    dense, _, _ = _route(tile_ref[...], disp, slots, up=True)
    o_ref[...] = dense.astype(o_ref.dtype)


def _slot_columns(k: int) -> int:
    """Word-major columns that hold k packed values."""
    return -(-k // _WORD)


def _word_tiles(tiles: jax.Array) -> jax.Array:
    """(nb, B) -> (nb, 32, B/32): column w holds bitmap word w's elements."""
    nb, block = tiles.shape
    return tiles.reshape(nb, block // _WORD, _WORD).transpose(0, 2, 1)


def _from_word_tiles(tiles: jax.Array) -> jax.Array:
    nb = tiles.shape[0]
    return tiles.transpose(0, 2, 1).reshape(nb, -1)


def _wire_specs(kc: int, W: int):
    return [_tile_spec((_WORD, kc)), _tile_spec((1, W))]


def _wire_shapes(tiles: jax.Array, kc: int, W: int):
    nb = tiles.shape[0]
    return [_out((nb, _WORD, kc), tiles.dtype, tiles),
            _out((nb, 1, W), jnp.int32, tiles)]


def _to_wire(values: jax.Array, words: jax.Array, k: int
             ) -> Tuple[jax.Array, jax.Array]:
    return (_from_word_tiles(values)[:, :k],
            jax.lax.bitcast_convert_type(words[:, 0, :], jnp.uint32))


def _check_block(block: int) -> None:
    if block % _WORD:
        raise ValueError(f"block must be a multiple of 32, got {block}")


def encode_topk(x: jax.Array, k_per_block: int, block: int = DEFAULT_BLOCK,
                interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Fused wire encode: (values (nb, k) in index order, bitmap (nb, B/32)
    uint32) in one pallas_call per tile.  Exactly k slots per block."""
    if x.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        raise TypeError(f"unsupported dtype {x.dtype}")
    _check_block(block)
    k = int(min(max(k_per_block, 1), block))
    kc, W = _slot_columns(k), block // _WORD
    tiles, _, _ = _prep(x, block)
    tiles = _word_tiles(tiles)
    nb = tiles.shape[0]
    values, words = pl.pallas_call(
        functools.partial(_encode_block_kernel, k=k),
        grid=(nb,),
        in_specs=[_tile_spec((_WORD, W))],
        out_specs=_wire_specs(kc, W),
        out_shape=_wire_shapes(tiles, kc, W),
        interpret=interpret,
    )(tiles)
    return _to_wire(values, words, k)


def ef_encode_topk(x: jax.Array, residual: jax.Array, k_per_block: int,
                   block: int = DEFAULT_BLOCK, interpret: bool = True
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused error-feedback wire encode: compress (x + residual) and emit
    (values, bitmap, new_residual) — residual update in the same kernel."""
    _check_block(block)
    k = int(min(max(k_per_block, 1), block))
    kc, W = _slot_columns(k), block // _WORD
    tiles, n, shape = _prep(x, block)
    rtiles, _, _ = _prep(residual, block)
    tiles, rtiles = _word_tiles(tiles), _word_tiles(rtiles)
    nb = tiles.shape[0]
    tile_spec = _tile_spec((_WORD, W))
    values, words, newr = pl.pallas_call(
        functools.partial(_ef_encode_block_kernel, k=k),
        grid=(nb,),
        in_specs=[tile_spec, tile_spec],
        out_specs=_wire_specs(kc, W) + [tile_spec],
        out_shape=_wire_shapes(tiles, kc, W)
        + [_out(tiles.shape, tiles.dtype, tiles)],
        interpret=interpret,
    )(tiles, rtiles)
    values, bitmap = _to_wire(values, words, k)
    newr = _from_word_tiles(newr).reshape(-1)[:n].reshape(shape)
    return values, bitmap, newr


def decode_topk(values: jax.Array, bitmap: jax.Array,
                shape: Tuple[int, ...], interpret: bool = True) -> jax.Array:
    """Inverse of :func:`encode_topk`: dense tensor of ``shape``."""
    nb, k = values.shape
    W = bitmap.shape[1]
    kc = _slot_columns(k)
    values = _word_tiles(jnp.pad(values, ((0, 0), (0, kc * _WORD - k))))
    words = jax.lax.bitcast_convert_type(bitmap, jnp.int32)
    dense = pl.pallas_call(
        _decode_block_kernel,
        grid=(nb,),
        in_specs=_wire_specs(kc, W),
        out_specs=_tile_spec((_WORD, W)),
        out_shape=_out((nb, _WORD, W), values.dtype, values),
        scratch_shapes=[pltpu.VMEM((_WORD, W), jnp.float32)],
        interpret=interpret,
    )(values, words.reshape(nb, 1, W))
    n = int(np.prod(shape))
    return _from_word_tiles(dense).reshape(-1)[:n].reshape(shape)
