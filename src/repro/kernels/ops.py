"""Jitted public wrappers over the Pallas Top-K kernels, plus the kernel
dispatch policy used by the compression hot path.

``topk_mask(x, k)`` matches :func:`repro.core.compression.topk_mask`'s
global-k signature by converting the global k into a per-block k (ceil
split).  Global and blockwise selections differ (documented: blockwise is
the standard approximation real compression kernels ship — it bounds the
worst-case block and parallelizes perfectly); convergence benchmarks compare
both (benchmarks/convergence.py).

Dispatch policy
---------------
Every ``use_kernel`` argument on the hot path (``compress_for_edge``,
``boundary_compress``, ``ef_compress``, ``topk_mask``) accepts a policy,
resolved here by :func:`resolve_policy` into an execution mode:

* ``False`` / ``None`` / ``"off"`` -> ``"global"`` — the legacy global
  top-k XLA formulation (bit-compatible with the historical default).
* ``"auto"`` -> ``"pallas"`` (compiled kernels) on a TPU backend, else
  ``"xla"`` — the fused blockwise oracle jitted under XLA, which has the
  *same* tie-capped selection semantics as the kernels, so numerics do not
  change when the job moves between CPU CI and TPU hardware.
* ``True`` / ``"force"`` -> the Pallas kernels even off-TPU
  (``"interpret"`` mode on CPU — slow, for parity debugging).

Policies are plain hashable scalars, so they travel safely through
``jax.jit`` static args and ``custom_vjp`` nondiff args.
"""
from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as kref
from . import topk_compress as tk

Policy = Union[bool, str, None]

#: policy values accepted by ``resolve_policy``
POLICIES = (False, True, None, "off", "auto", "force")


def off_tpu() -> bool:
    """Pallas interpret mode exactly when no TPU backs the default device:
    compiled kernels on the chip, the interpreter everywhere else."""
    return jax.default_backend() != "tpu"


def resolve_policy(policy: Policy) -> str:
    """Map a ``use_kernel`` policy to an execution mode: ``"global"``
    (legacy global top-k XLA), ``"xla"`` (fused blockwise XLA fallback),
    ``"interpret"`` (Pallas interpret mode), or ``"pallas"`` (compiled)."""
    if policy is None or policy is False or policy == "off":
        return "global"
    if policy is True or policy == "force":
        return "interpret" if off_tpu() else "pallas"
    if policy == "auto":
        return "xla" if off_tpu() else "pallas"
    raise ValueError(
        f"unknown kernel dispatch policy {policy!r}; expected one of "
        f"{POLICIES}")


def per_block_k(n: int, k: int, block: int = tk.DEFAULT_BLOCK) -> int:
    """Global k -> per-block k (ceil split over the tile grid)."""
    nb = -(-int(n) // block)
    return max(1, -(-int(k) // nb))


# ------------------------------------------------------------ dense masks --

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _blockwise_topk_mask(x, k_per_block, block, interpret):
    return tk.blockwise_topk_mask(x, k_per_block, block, interpret=interpret)


def blockwise_topk_mask(x: jax.Array, k_per_block: int,
                        block: int = tk.DEFAULT_BLOCK) -> jax.Array:
    return _blockwise_topk_mask(x, k_per_block, block, off_tpu())


def topk_mask(x: jax.Array, k: int, block: int = tk.DEFAULT_BLOCK) -> jax.Array:
    """Global-k API -> per-block k (keeps ~k total, exact per block)."""
    n = int(np.prod(x.shape))
    return blockwise_topk_mask(x, per_block_k(n, k, block), block)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _ef_topk(x, residual, k_per_block, block, interpret):
    return tk.ef_topk(x, residual, k_per_block, block, interpret=interpret)


def ef_topk(x: jax.Array, residual: jax.Array, k_per_block: int,
            block: int = tk.DEFAULT_BLOCK):
    return _ef_topk(x, residual, k_per_block, block, off_tpu())


# ------------------------------------------------- fused encode / decode --

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _encode_pallas(x, k_per_block, block, interpret):
    return tk.encode_topk(x, k_per_block, block, interpret=interpret)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _decode_pallas(values, bitmap, shape, interpret):
    return tk.decode_topk(values, bitmap, shape, interpret=interpret)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _ef_encode_pallas(x, residual, k_per_block, block, interpret):
    return tk.ef_encode_topk(x, residual, k_per_block, block,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnums=(1, 2))
def xla_encode_topk(x: jax.Array, k_per_block: int,
                    block: int = tk.DEFAULT_BLOCK):
    """Fused blockwise encode under plain XLA — the CPU fallback of the
    ``"auto"`` policy (same selection semantics as the Pallas kernel)."""
    return kref.encode_topk_ref(x, k_per_block, block)


@functools.partial(jax.jit, static_argnums=(2,))
def xla_decode_topk(values: jax.Array, bitmap: jax.Array,
                    shape: Tuple[int, ...]):
    return kref.decode_topk_ref(values, bitmap, shape)


@functools.partial(jax.jit, static_argnums=(2, 3))
def xla_ef_encode_topk(x: jax.Array, residual: jax.Array, k_per_block: int,
                       block: int = tk.DEFAULT_BLOCK):
    return kref.ef_encode_topk_ref(x, residual, k_per_block, block)


def encode_topk(x: jax.Array, k_per_block: int,
                block: int = tk.DEFAULT_BLOCK, interpret=None):
    """Jitted fused wire encode (Pallas): (values, bitmap)."""
    return _encode_pallas(x, k_per_block, block,
                          off_tpu() if interpret is None else interpret)


def decode_topk(values: jax.Array, bitmap: jax.Array,
                shape: Tuple[int, ...], interpret=None):
    return _decode_pallas(values, bitmap, tuple(shape),
                          off_tpu() if interpret is None else interpret)


def ef_encode_topk(x: jax.Array, residual: jax.Array, k_per_block: int,
                   block: int = tk.DEFAULT_BLOCK, interpret=None):
    return _ef_encode_pallas(x, residual, k_per_block, block,
                             off_tpu() if interpret is None else interpret)


# ------------------------------------------------------- codec round trip --

def codec_topk_mask(x: jax.Array, k: int, mode: str,
                    block: int = tk.DEFAULT_BLOCK) -> jax.Array:
    """Wire-faithful sparsification: fused encode (threshold search + bitmap
    + packed-value compaction) then decode — the consumer sees exactly what
    the "mask" wire encoding carried.  ``mode`` is a resolved policy."""
    n = int(np.prod(x.shape))
    kpb = per_block_k(n, k, block)
    if mode == "xla":
        values, bitmap = xla_encode_topk(x, kpb, block)
        return xla_decode_topk(values, bitmap, x.shape)
    interpret = mode != "pallas"
    values, bitmap = encode_topk(x, kpb, block, interpret=interpret)
    return decode_topk(values, bitmap, x.shape, interpret=interpret)


def codec_ef_topk(x: jax.Array, residual: jax.Array, k: int, mode: str,
                  block: int = tk.DEFAULT_BLOCK
                  ) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback codec round trip: (sent, new_residual), residual
    update fused into the encode kernel."""
    n = int(np.prod(x.shape))
    kpb = per_block_k(n, k, block)
    if mode == "xla":
        values, bitmap, newr = xla_ef_encode_topk(x, residual, kpb, block)
        return xla_decode_topk(values, bitmap, x.shape), newr
    interpret = mode != "pallas"
    values, bitmap, newr = ef_encode_topk(x, residual, kpb, block,
                                          interpret=interpret)
    return decode_topk(values, bitmap, x.shape, interpret=interpret), newr
