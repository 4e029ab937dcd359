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
so among threshold ties the first ``k - n_above`` in index order win.

Layout.  The dense kernels give every grid step one block as a
row-major ``(block/128, 128)`` tile.  The wire kernels give every grid step
128 blocks, one per lane: the step reads a ``(128, block)`` slab of the
``(nb, block)`` row-major view (no HBM transpose; the block count is padded
to a multiple of 128 with zero blocks that are sliced away) and transposes
it in VMEM to ``(block, 128)``, element ``j`` of block ``g`` at ``[j, g]``.
Every step of the selection is then elementwise work over whole vregs with
no cross-lane traffic: each search pass's count is a sum down the rows, the
tie rank and slot index are a log-step shift-and-add down the rows
(shifts of 8 rows or more move whole vregs).
Bitmap word ``w`` of a block is rows ``32w .. 32w+31`` of its lane, read
with strided loads.  Mosaic lowers no gather or scatter, so packing the
kept values is a log-step compaction network: each kept element moves down
its lane by the set bits of its displacement, one shift-and-select step per
bit (``log2(block)`` steps whatever k is), and decoding runs the same
network in reverse.  The packed values and the bitmap words go back to
``(128, k)`` and ``(128, block/32)`` through small in-VMEM transposes, so
the HBM wire tensors are ``values`` (nb, k) in index order and ``bitmap``
(nb, block/32) uint32, LSB-first.  On a TPU v5e, at the gpt2-xl RAD step's
logits edge (4 x 1024 x 50432 f32, 484 of every 4096 kept), an encode
takes 18.8 ms of device time and a decode 14.2 ms, 0.37 and 0.28 us a
block; with one block per grid step as a (32, 128) tile they took 339 and
123 ms, every pass waiting on the one before.

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
_LANE = 128                 # TPU lane width: wire blocks per grid step
_WORD = 32                  # bits per bitmap word
_EMPTY = -2 ** 31           # displacement of a row that holds no kept
                            # element: no bit of a shift is set in it
_VMEM_LIMIT = 48 * 2 ** 20  # a wire step's (block, 128) f32 working set
                            # outgrows the default scoped VMEM (16 MiB on
                            # a v5e; the logits edge's kernels need 32)


def _count(mask: jax.Array, axes: Tuple[int, ...]) -> jax.Array:
    """Number of true entries over ``axes``, kept as size-1 dims."""
    return jnp.sum(mask.astype(jnp.int32), axis=axes, keepdims=True)


def _kth_threshold_bits(mag_bits: jax.Array, k: int,
                        axes: Tuple[int, ...] = (0, 1)) -> jax.Array:
    """Largest t such that count(mag_bits >= t) >= k over ``axes`` (t=0 if
    k >= n), with ``axes`` kept as size-1 dims: (1, 1) for a dense tile,
    (1, 128) for a wire slab, one threshold per lane.

    mag_bits: int32 bit patterns of non-negative floats (monotone in value).
    31 fixed iterations of compare+reduce — branch-free, VPU-only.
    """
    shape = tuple(1 if a in axes else n
                  for a, n in enumerate(mag_bits.shape))
    lo = jnp.zeros(shape, jnp.int32)
    hi = jnp.full(shape, 0x7F800000, jnp.int32)  # +inf bounds every
    # magnitude (also keeps hi - lo + 1 inside int32 — 2^31-1 would overflow)

    def body(_, carry):
        lo, hi = carry
        mid = lo + ((hi - lo + 1) >> 1)
        take = _count(mag_bits >= mid, axes) >= k
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


def _prep(x: jax.Array, block: int, group: int = 1
          ) -> Tuple[jax.Array, int, Tuple[int, ...]]:
    """(nb, block) zero-padded blocks, nb a multiple of ``group``."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    nb = -(-n // (block * group)) * group
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
# Fused wire-encode / decode kernels: 128 blocks per grid step, one per lane
# ---------------------------------------------------------------------------

def _rows(shape) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _shift_rows(v: jax.Array, s: int, fill) -> jax.Array:
    """Every lane moved ``s`` rows toward higher rows (``s < 0``: toward
    lower ones), ``fill`` entering at the vacated end.  A multiple of 8
    rows moves whole vregs."""
    pad = jnp.full((abs(s),) + v.shape[1:], fill, v.dtype)
    if s > 0:
        return jnp.concatenate([pad, v[:-s]])
    return jnp.concatenate([v[-s:], pad])


def _prefix_rows(m: jax.Array) -> jax.Array:
    """Inclusive prefix sum down the rows of every lane: log-step
    shift-and-add."""
    d = 1
    while d < m.shape[0]:
        m = m + _shift_rows(m, d, 0)
        d *= 2
    return m


def _select(x32: jax.Array, k: int) -> jax.Array:
    """Tie-capped selection in every lane of a (block, 128) slab, as each
    kept element's displacement to its packed slot (its row minus the
    number kept before it), ``_EMPTY`` where nothing is kept.  Kept:
    everything strictly above the k-th largest bit pattern, plus the first
    ``k - n_above`` threshold ties in row order."""
    bits = _mag_bits(x32)
    thr = _kth_threshold_bits(bits, k, axes=(0,))
    above = bits > thr
    tie = bits == thr
    # one scan counts both: elements above in the low half-word, ties in
    # the high one (a block has fewer than 2^15 elements)
    c = _prefix_rows(above.astype(jnp.int32) + (tie.astype(jnp.int32) << 16))
    n_above = c & 0xFFFF
    cap = k - n_above[-1:]                  # ties that still fit
    ties = c >> 16
    keep = above | (tie & (ties <= cap))
    kept = n_above + jnp.minimum(ties, cap)  # kept up to and including j
    return jnp.where(keep, _rows(x32.shape) + 1 - kept, _EMPTY)


def _route(disp: jax.Array, vals=None, up: bool = False):
    """Move every kept entry ``disp`` rows along its lane — down
    (compaction) taking the bits of ``disp`` lowest first, or up
    (expansion) highest first, exactly undoing a compaction.  Down, the
    kept elements stay in strictly increasing rows after every step (their
    displacements never decrease along the lane, and two of them are at
    least as far apart as their displacements differ), so no step lands one
    element on another: a row holds at most one of a lander and a stayer,
    and ``_EMPTY`` is below every displacement.  ``vals`` travel with
    ``disp``; rows an entry leaves keep a stale value.  Returns
    (disp, vals)."""
    nbits = max(1, (disp.shape[0] - 1).bit_length())
    for t in (reversed(range(nbits)) if up else range(nbits)):
        s = (1 << t) * (1 if up else -1)
        mover = (disp & (1 << t)) != 0
        moved = _shift_rows(jnp.where(mover, disp, _EMPTY), s, _EMPTY)
        if vals is not None:
            vals = jnp.where(moved != _EMPTY, _shift_rows(vals, s, 0), vals)
        disp = jnp.maximum(moved, jnp.where(mover, _EMPTY, disp))
    return disp, vals


def _value_rows(k: int, block: int) -> int:
    """Rows of a packed slab that hold k slots, in whole 128-row transposes."""
    return min(block, -(-k // _LANE) * _LANE)


def _encode_lanes(x32: jax.Array, k: int, v_ref, m_ref, keep_ref) -> None:
    """Select, then write each lane's keep-mask to ``keep_ref``, its bitmap
    words (LSB-first, as int32 bit patterns) and its kept values packed in
    index order."""
    disp = _select(x32, k)
    keep_ref[...] = (disp != _EMPTY).astype(jnp.int32)
    words = keep_ref[pl.ds(0, m_ref.shape[1], stride=_WORD), :]
    for b in range(1, _WORD):
        words = words | (keep_ref[pl.ds(b, m_ref.shape[1], stride=_WORD), :]
                         << b)
    m_ref[...] = words.T
    _, packed = _route(disp, x32)
    kr = _value_rows(k, x32.shape[0])
    v_ref[...] = packed[:kr].T[:, :v_ref.shape[1]].astype(v_ref.dtype)


def _encode_kernel(x_ref, v_ref, m_ref, keep_ref, *, k: int):
    _encode_lanes(x_ref[...].astype(jnp.float32).T, k, v_ref, m_ref,
                  keep_ref)


def _ef_encode_kernel(x_ref, r_ref, v_ref, m_ref, newr_ref, keep_ref, *,
                      k: int):
    corrected = _round_to(x_ref[...].astype(jnp.float32)
                          + r_ref[...].astype(jnp.float32), x_ref.dtype).T
    _encode_lanes(corrected, k, v_ref, m_ref, keep_ref)
    newr_ref[...] = jnp.where(keep_ref[...] == 1, 0.0, corrected).T.astype(
        newr_ref.dtype)


def _decode_kernel(v_ref, m_ref, o_ref, slots_ref, keep_ref):
    """Expand the packed values back to their rows: the displacements are
    compacted like the values were, then every value retraces its
    compaction in reverse."""
    words = m_ref[...].T
    for b in range(_WORD):
        keep_ref[pl.ds(b, words.shape[0], stride=_WORD), :] = (
            (words >> b) & 1)
    keep = keep_ref[...]
    block, k = keep.shape[0], v_ref.shape[1]
    disp = jnp.where(keep == 1, _rows(keep.shape) + 1 - _prefix_rows(keep),
                     _EMPTY)
    disp, _ = _route(disp)
    slots_ref[...] = jnp.zeros(slots_ref.shape, jnp.float32)
    slots_ref[:, :k] = v_ref[...].astype(jnp.float32)
    vals = slots_ref[...].T
    if vals.shape[0] < block:
        vals = jnp.concatenate(
            [vals, jnp.zeros((block - vals.shape[0], vals.shape[1]),
                             jnp.float32)])
    _, dense = _route(disp, vals, up=True)
    o_ref[...] = jnp.where(keep == 1, dense, 0.0).T.astype(o_ref.dtype)


def _slab_spec(width: int) -> pl.BlockSpec:
    """128 consecutive rows of an (nb, width) operand per grid step."""
    return pl.BlockSpec((_LANE, width), lambda i: (i, 0))


def _wire_call(kernel, ins, outs, scratch, interpret: bool):
    """One pallas_call over slabs of 128 blocks (nb a multiple of 128)."""
    return pl.pallas_call(
        kernel,
        grid=(ins[0].shape[0] // _LANE,),
        in_specs=[_slab_spec(a.shape[1]) for a in ins],
        out_specs=[_slab_spec(o.shape[1]) for o in outs],
        out_shape=outs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*ins)


def _encode_outs(tiles: jax.Array, k: int):
    nb, block = tiles.shape
    return [_out((nb, k), tiles.dtype, tiles),
            _out((nb, block // _WORD), jnp.int32, tiles)]


def _to_wire(values: jax.Array, words: jax.Array, nb: int
             ) -> Tuple[jax.Array, jax.Array]:
    return values[:nb], jax.lax.bitcast_convert_type(words[:nb], jnp.uint32)


def _wire_k(k_per_block: int, block: int) -> int:
    """Slots per block; the block must fill whole bitmap words and keep
    its counts inside a half-word."""
    if block % _WORD or not 0 < block < 2 ** 15:
        raise ValueError(
            f"block must be a multiple of 32 below 2^15, got {block}")
    return int(min(max(k_per_block, 1), block))


def encode_topk(x: jax.Array, k_per_block: int, block: int = DEFAULT_BLOCK,
                interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Fused wire encode: (values (nb, k) in index order, bitmap (nb, B/32)
    uint32) in one pallas_call.  Exactly k slots per block."""
    if x.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        raise TypeError(f"unsupported dtype {x.dtype}")
    k = _wire_k(k_per_block, block)
    tiles, n, _ = _prep(x, block, _LANE)
    values, words = _wire_call(
        functools.partial(_encode_kernel, k=k), [tiles],
        _encode_outs(tiles, k), [pltpu.VMEM((block, _LANE), jnp.int32)],
        interpret)
    return _to_wire(values, words, -(-n // block))


def ef_encode_topk(x: jax.Array, residual: jax.Array, k_per_block: int,
                   block: int = DEFAULT_BLOCK, interpret: bool = True
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused error-feedback wire encode: compress (x + residual) and emit
    (values, bitmap, new_residual) — residual update in the same kernel."""
    k = _wire_k(k_per_block, block)
    tiles, n, shape = _prep(x, block, _LANE)
    rtiles, _, _ = _prep(residual, block, _LANE)
    values, words, newr = _wire_call(
        functools.partial(_ef_encode_kernel, k=k), [tiles, rtiles],
        _encode_outs(tiles, k) + [_out(tiles.shape, tiles.dtype, tiles)],
        [pltpu.VMEM((block, _LANE), jnp.int32)], interpret)
    values, bitmap = _to_wire(values, words, -(-n // block))
    return values, bitmap, newr.reshape(-1)[:n].reshape(shape)


def decode_topk(values: jax.Array, bitmap: jax.Array,
                shape: Tuple[int, ...], interpret: bool = True) -> jax.Array:
    """Inverse of :func:`encode_topk`: dense tensor of ``shape``."""
    nb, k = values.shape
    block = bitmap.shape[1] * _WORD
    pad = ((0, -nb % _LANE), (0, 0))
    values = jnp.pad(values, pad)
    words = jnp.pad(jax.lax.bitcast_convert_type(bitmap, jnp.int32), pad)
    (dense,) = _wire_call(
        _decode_kernel, [values, words],
        [_out((values.shape[0], block), values.dtype, values)],
        [pltpu.VMEM((_LANE, _value_rows(k, block)), jnp.float32),
         pltpu.VMEM((block, _LANE), jnp.int32)], interpret)
    n = int(np.prod(shape))
    return dense.reshape(-1)[:n].reshape(shape)
