"""Pure-jnp oracles for the Top-K compression kernels.

Selection semantics (shared by oracle and kernel, so comparisons are exact):
keep every element whose |value| is >= the k-th largest |value| in its block.
With ties at the threshold this keeps a *superset* of k elements — the same
superset in both implementations, because the kernel's binary search over
IEEE-754 bit patterns recovers exactly the k-th largest magnitude.

The *encode* oracles are different: a wire payload has fixed capacity, so
ties at the threshold are capped — among threshold-tied elements the first
``k - n_above`` in index order are kept, giving exactly ``min(k, block)``
slots per block.  The fused Pallas encode kernels implement the same rule,
so encode comparisons are also exact.

Wire format (the "mask" encoding priced by
:func:`repro.core.compression.wire_bytes`): per block of ``B`` elements
(``B`` a multiple of 32), a bitmap of ``B/32`` uint32 words (LSB-first
within each word) plus ``k`` packed values in index order.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def topk_mask_ref(x: jax.Array, k: int) -> jax.Array:
    """Global Top-K by magnitude, dense output (threshold semantics)."""
    flat = x.reshape(-1)
    k = int(min(max(k, 1), flat.shape[0]))
    vals, _ = jax.lax.top_k(jnp.abs(flat).astype(jnp.float32), k)
    thr = vals[-1]
    keep = jnp.abs(flat).astype(jnp.float32) >= thr
    return jnp.where(keep, flat, 0).reshape(x.shape)


def _pad_to_blocks(flat: jax.Array, block: int) -> Tuple[jax.Array, int]:
    n = flat.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    return jnp.pad(flat, (0, pad)), nb


def blockwise_topk_mask_ref(x: jax.Array, k_per_block: int,
                            block: int = 4096) -> jax.Array:
    """Blockwise Top-K (what the TPU kernel computes): the flat tensor is
    split into ``block``-sized tiles, each keeping its own top k_per_block.
    Zero padding never wins selection (|0| below any positive threshold)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    padded, nb = _pad_to_blocks(flat, block)
    tiles = padded.reshape(nb, block)
    k = int(min(max(k_per_block, 1), block))
    mags = jnp.abs(tiles).astype(jnp.float32)
    # barrier: XLA rewrites slice-of-top_k into a full per-row sort
    vals = jax.lax.optimization_barrier(jax.lax.top_k(mags, k)[0])
    thr = vals[:, -1:]
    out = jnp.where(mags >= thr, tiles, 0)
    return out.reshape(-1)[:n].reshape(x.shape)


def _force_rounding(x: jax.Array) -> jax.Array:
    """Pin storage-dtype rounding of a computed value: under jit, XLA on
    CPU can keep a bf16 sum in f32 on the path into the selection bitcast,
    diverging from the eagerly-rounded value.  (The kernels, which Mosaic
    compiles without ``reduce_precision``, sum in f32 and round through an
    ``astype`` round trip: the same value.)"""
    if x.dtype == jnp.bfloat16:
        return jax.lax.reduce_precision(x, 8, 7)
    if x.dtype == jnp.float16:
        return jax.lax.reduce_precision(x, 5, 10)
    return x


def ef_topk_ref(x: jax.Array, residual: jax.Array, k_per_block: int,
                block: int = 4096) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback variant: compress (x + residual), return
    (sent, new_residual)."""
    corrected = _force_rounding(x + residual)
    sent = blockwise_topk_mask_ref(corrected, k_per_block, block)
    return sent, corrected - sent


def count_kept(x: jax.Array) -> int:
    return int(jnp.sum(x != 0))


# ---------------------------------------------------------------------------
# Fused wire-encode / decode oracles (tie-capped, fixed wire capacity)
# ---------------------------------------------------------------------------

def _mag_bits(tiles: jax.Array) -> jax.Array:
    """int32 bit patterns of |tiles| as float32 — order-isomorphic to the
    magnitude for non-negative floats (exactly what the kernel searches)."""
    return jax.lax.bitcast_convert_type(
        jnp.abs(tiles.astype(jnp.float32)), jnp.int32)


def _keep_capped(bits: jax.Array, k: int) -> jax.Array:
    """Boolean keep-mask with exactly min(k, B) kept per row: everything
    strictly above the k-th largest bit pattern, plus the first
    ``k - n_above`` threshold ties in index order.

    The threshold runs ``top_k`` on the *float* view of the bit patterns
    (order-isomorphic for the non-negative magnitudes ``_mag_bits``
    produces, so the selected element is identical): XLA:CPU's fast TopK
    custom call is float-only — an integer top_k falls back to a full
    sort, ~30x slower at bench shapes.  The ``optimization_barrier``
    stops XLA from rewriting slice-of-top_k back into that same sort."""
    mags = jax.lax.bitcast_convert_type(bits, jnp.float32)
    thr_m = jax.lax.optimization_barrier(jax.lax.top_k(mags, k)[0])[:, -1:]
    thr = jax.lax.bitcast_convert_type(thr_m, jnp.int32)
    above = bits > thr
    n_above = jnp.sum(above.astype(jnp.int32), axis=1, keepdims=True)
    tie = bits == thr
    tie_rank = jnp.cumsum(tie.astype(jnp.int32), axis=1)
    return above | (tie & (tie_rank <= (k - n_above)))


def pack_mask_ref(keep: jax.Array) -> jax.Array:
    """(nb, B) bool -> (nb, B//32) uint32 bitmap, LSB-first per word."""
    nb, B = keep.shape
    w = keep.reshape(nb, B // 32, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(w << shifts, axis=2, dtype=jnp.uint32)


def unpack_mask_ref(bitmap: jax.Array) -> jax.Array:
    """(nb, W) uint32 bitmap -> (nb, W*32) bool keep-mask."""
    nb, W = bitmap.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (bitmap[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.astype(bool).reshape(nb, W * 32)


def encode_topk_ref(x: jax.Array, k_per_block: int,
                    block: int = 4096) -> Tuple[jax.Array, jax.Array]:
    """Fused wire encode: (values (nb, k) in index order, bitmap (nb, B/32)
    uint32).  Tie-capped — exactly k slots per block, the wire's capacity."""
    if block % 32:
        raise ValueError(f"block must be a multiple of 32, got {block}")
    flat = x.reshape(-1)
    padded, nb = _pad_to_blocks(flat, block)
    tiles = padded.reshape(nb, block)
    k = int(min(max(k_per_block, 1), block))
    # lax.top_k is index-stable on ties (lower index first), so its index
    # set IS the tie-capped keep set _keep_capped specifies — one fast-path
    # TopK call replaces the dense mask + cumsum + compaction pipeline
    # (tested equivalent against _keep_capped across dtypes/ties/zeros)
    mags = jnp.abs(tiles).astype(jnp.float32)
    idx = jnp.sort(jax.lax.top_k(mags, k)[1], axis=1)    # index order
    values = jnp.take_along_axis(tiles, idx, axis=1)
    word = (idx >> 5).astype(jnp.int32)
    bit = (idx & 31).astype(jnp.uint32)
    rows = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32)[:, None],
                            idx.shape)
    bitmap = jnp.zeros((nb, block // 32), jnp.uint32).at[rows, word].add(
        jnp.uint32(1) << bit)
    return values, bitmap


def decode_topk_ref(values: jax.Array, bitmap: jax.Array,
                    shape: Tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`encode_topk_ref`: dense tensor of ``shape``."""
    keep = unpack_mask_ref(bitmap)
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    idx = jnp.clip(pos, 0, values.shape[1] - 1)
    dense = jnp.where(keep, jnp.take_along_axis(values, idx, axis=1), 0)
    n = int(np.prod(shape))
    return dense.reshape(-1)[:n].reshape(shape)


def ef_encode_topk_ref(x: jax.Array, residual: jax.Array, k_per_block: int,
                       block: int = 4096
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused error-feedback wire encode: compress (x + residual), return
    (values, bitmap, new_residual) with new_residual = unsent corrected."""
    corrected = _force_rounding(x + residual)
    values, bitmap = encode_topk_ref(corrected, k_per_block, block)
    sent = decode_topk_ref(values, bitmap, corrected.shape)
    return values, bitmap, (corrected - sent).astype(x.dtype)
