"""Kernel micro-benchmark (paper §6: compression must outrun the wire).

Three measurements, all on whatever backend is present:

* the legacy unfused wire path the fused kernels replace — global
  ``topk_select`` (full-tensor top-k + gather), then a separate scatter
  into a dense keep-mask, a separate bitmap pack, each its own XLA op;
* the fused blockwise encode (``xla_encode_topk`` — the ``"auto"``
  policy's CPU fallback, identical tie-capped selection semantics to the
  Pallas kernel) and its EF variant;
* interpret-mode Pallas parity against the XLA oracle on a small tensor
  (structural correctness — interpret wall time itself is meaningless),
  plus the compiled kernel's structural stats (VMEM tile bytes, grid,
  threshold-search passes).  None of these is a TPU timing: on a TPU the
  kernels run compiled, chosen by the platform, and are not timed here
  (README "Kernels").

The returned result dict carries ``speedup`` (unfused / fused seconds) as
the tracked metric for the BENCH artifact.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import topk_select
from repro.kernels import ops as kops
from repro.kernels import topk_compress as tk


def _time(fn, *args, reps=7):
    jax.block_until_ready(fn(*args))       # one warm-up, whole result tree
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))         # robust to GC / scheduler noise


def _unfused_encode(n: int, nb: int, block: int, k_total: int):
    """The replaced hot path, jitted: global select, then mask scatter and
    bitmap pack as separate ops over the full tensor."""
    shifts = jnp.arange(32, dtype=jnp.uint32)

    @jax.jit
    def encode(v):
        flat = v.reshape(-1)
        values, idx = topk_select(flat, k_total)
        keep = jnp.zeros((n,), jnp.bool_).at[idx].set(True)
        words = keep.reshape(-1, 32).astype(jnp.uint32)
        bitmap = jnp.sum(words << shifts[None, :], axis=1,
                         dtype=jnp.uint32).reshape(nb, block // 32)
        return values, bitmap

    return encode


def run(csv_writer):
    n = 1 << 20
    block = tk.DEFAULT_BLOCK
    nb = n // block
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n), jnp.float32)
    k = n // 100
    kpb = kops.per_block_k(n, k, block)
    k_total = nb * kpb                      # equal wire payload both paths

    unfused = _unfused_encode(n, nb, block, k_total)
    fused = jax.jit(lambda v: kops.xla_encode_topk(v, kpb, block))
    r0 = jnp.zeros_like(x)
    fused_ef = jax.jit(
        lambda v, r: kops.xla_ef_encode_topk(v, r, kpb, block))

    t_unfused = _time(unfused, x)
    t_fused = _time(fused, x)
    t_fused_ef = _time(fused_ef, x, r0)
    speedup = t_unfused / max(t_fused, 1e-12)
    csv_writer("kernel_unfused_select_encode", t_unfused * 1e6,
               f"n={n},k={k_total},global topk_select + scatter + pack")
    csv_writer("kernel_fused_encode_xla", t_fused * 1e6,
               f"n={n},k_per_block={kpb},speedup={speedup:.2f}x")
    csv_writer("kernel_fused_ef_encode_xla", t_fused_ef * 1e6,
               f"n={n},k_per_block={kpb},residual update fused")

    # interpret-mode Pallas parity vs the XLA oracle (small tensor: the
    # interpreter is slow, and parity is independent of size)
    ns = 1 << 14
    xs = jnp.asarray(np.random.default_rng(1).standard_normal(ns),
                     jnp.float32)
    ks = kops.per_block_k(ns, ns // 100, block)
    v_i, m_i = kops.encode_topk(xs, ks, block, interpret=True)
    v_x, m_x = kops.xla_encode_topk(xs, ks, block)
    parity = bool(jnp.array_equal(v_i, v_x) and jnp.array_equal(m_i, m_x))
    rt = kops.decode_topk(v_i, m_i, xs.shape, interpret=True)
    rt_ok = bool(jnp.array_equal(rt, kops.xla_decode_topk(v_x, m_x,
                                                          xs.shape)))
    csv_writer("kernel_interpret_parity", 0.0,
               f"encode={'ok' if parity else 'MISMATCH'},"
               f"roundtrip={'ok' if rt_ok else 'MISMATCH'}")

    # structural stats of the compiled Pallas wire kernels (f32): a grid
    # step owns 128 blocks, one per lane, and holds the (128, block) input
    # slab, its (block, 128) keep-mask scratch, the (128, k) value slots
    # and the (128, block/32) bitmap words
    group = tk._LANE
    grid = -(-nb // group)
    vmem_bytes = 4 * group * (2 * block + kpb + block // tk._WORD)
    csv_writer("kernel_pallas_structure", 0.0,
               f"block={block},vmem_bytes={vmem_bytes},"
               f"search_iters={tk._SEARCH_BITS},grid={grid},"
               f"value_slots={kpb}")
    return {"kernel": {
        "t_unfused_us": t_unfused * 1e6,
        "t_fused_us": t_fused * 1e6,
        "t_fused_ef_us": t_fused_ef * 1e6,
        "speedup": speedup,
        "parity": float(parity and rt_ok),
        "vmem_bytes": float(vmem_bytes),
        "grid": float(grid),
    }}
