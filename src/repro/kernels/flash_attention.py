"""Pallas TPU kernel: blocked (flash-style) attention for training.

``attention(q, k, v, causal)`` computes what ``models.attention.sdpa`` does
with a causal (or no) mask over self-attention, for q ``(B, S, H, hd)`` and
k/v ``(B, S, Hkv, hd)`` with ``H = g * Hkv``, without ever writing the
``(B, H, S, S)`` scores to HBM.

Layout.  The kernels see q, k and v as ``(B, H, hd, S)``: the sequence
along lanes, the head dimension along sublanes, so a ``(hd, block)`` tile
fills whole vregs at ``hd = 64``.  The projections that make q, k and v
write this layout themselves (XLA gives their matmuls a sequence-minor
output), so the transposes around the call compile to bitcasts.  The
softmax scale is folded into q before the call.

Forward.  The grid is ``(B, H, S/block, S/block)``, the key axis
innermost.  A step scores one ``(hd, block)`` query tile against one key
tile as ``k^T q`` in f32 (keys along rows, queries along lanes), and folds
the scores into a running max ``m``, a running sum ``l`` and an f32
accumulator (VMEM scratch, kept across the key axis).  The last step of a
query block writes ``acc / l`` and the log-sum-exp ``m + log l``, the only
statistic the backward needs: ``(B, H, 1, S)`` f32.

Backward.  One kernel, grid ``(B, H, S/block, S/block)``, the query axis
innermost.  A step recomputes ``p^T = exp(k^T q - lse)`` of one block
pair, accumulates ``dk`` and ``dv`` of its key block in VMEM scratch, and
adds its share of ``dq`` into an ``(S/block, hd, block)`` f32 scratch kept
for the whole ``(b, h)`` and written once.  ``delta = rowsum(o * do)`` is
computed by XLA before the call.  Under GQA the key and value tiles are
read from head ``h // g`` by the index maps, and ``dk``/``dv`` come out
per query head and are summed over the group after the call.

Causal.  Query and key blocks are the same size, so a block pair above
the diagonal is skipped (its index map repeats the tile of the step
before, and nothing is copied for it), one below it needs no mask, and
only the diagonal pair applies ``sdpa``'s mask: a masked score becomes
``NEG_INF``, whose exponential is 0.

Precision.  Scores, max, sum and accumulators are f32.  The dots follow the
rule XLA applies to ``sdpa``'s f32 einsums on a TPU: at the default matmul
precision one bf16 pass with f32 accumulation (the operands are rounded to
bf16 before the dot, as XLA rounds them), at any higher setting the full
f32 product.

One trace, one lowering.  The entry point ``_attention`` is a module-level
``jax.jit`` whose only static arguments are plain flags; block size,
scale and dot precision are derived inside it from the shapes and the
matmul-precision setting.  It wraps the module-level ``custom_vjp``
``_flash``, and the two ``pallas_call``s sit in module-level jits of their
own.  Every layer of a model calls the same jitted functions with the same
avals, so JAX traces the kernel once and lowers it once per program however
many layers call it (the lowering reuses one function per jaxpr).  A
closure, ``functools.partial`` or ``pallas_call`` built per call would be
traced and lowered once per layer and per direction, and would cost set-up
time in proportion.

Inside ``shard_map(check_vma=True)`` every ``out_shape`` carries the
query's ``vma``, as the codec's do.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30             # models.attention's masked score
_BLOCKS = (512, 256, 128)   # sequence blocks tried, largest first
_DQ_VMEM = 4 * 2 ** 20      # largest (S, hd) f32 dq scratch kept in VMEM
_VMEM_LIMIT = 64 * 2 ** 20  # a step's (block, block) f32 score tiles at 512
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
#: ``jax_default_matmul_precision`` values at which XLA takes one bf16
#: pass for an f32 dot on a TPU
_ONE_PASS = (None, "default", "bfloat16", "fastest")


def block_size(seq: int, head_dim: int) -> Optional[int]:
    """The query and key block for a sequence of ``seq``: the largest of
    512, 256, 128 that divides it, or None when none does or when the
    backward's ``(seq, head_dim)`` f32 dq scratch would not fit VMEM (the
    caller then keeps ``sdpa``)."""
    if seq * head_dim * 4 > _DQ_VMEM:
        return None
    return next((b for b in _BLOCKS if seq % b == 0), None)


def _dot(a, b, dims, one_pass: bool):
    if one_pass:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        precision = None
    else:
        precision = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _out(shape, dtype, like) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _diagonal_mask(s):
    """``sdpa``'s causal mask on the (keys, queries) scores of a diagonal
    block pair: key row r is seen by query lane c when c >= r."""
    keys = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    queries = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(queries >= keys, s, NEG_INF)


def _steps(step, causal: bool, q_block, kv_block):
    """Run ``step(masked)`` for a block pair: every pair without a mask
    when not causal; below the diagonal unmasked, on it masked, above it
    not at all when causal."""
    if not causal:
        step(False)
        return
    pl.when(kv_block < q_block)(lambda: step(False))
    pl.when(kv_block == q_block)(lambda: step(True))


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------- forward --

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                causal, one_pass):
    i, j = pl.program_id(2), pl.program_id(3)
    last = i if causal else pl.num_programs(3) - 1

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(masked):
        s = _dot(k_ref[...], q_ref[...], _TN, one_pass)      # (bk, bq)
        if masked:
            s = _diagonal_mask(s)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=0, keepdims=True)
        m_sc[...] = m_next
        acc_sc[...] = acc_sc[...] * alpha + _dot(v_ref[...], p, _NN,
                                                 one_pass)

    _steps(step, causal, i, j)

    @pl.when(j == last)
    def _done():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _attn_fwd_pallas(q, k, v, causal, block, one_pass, interpret):
    """(o, lse) for q (B, H, hd, S), k/v (B, Hkv, hd, S): o like q, lse
    (B, H, 1, S)."""
    B, H, hd, S = q.shape
    g = H // k.shape[1]
    n = S // block

    def kv_map(b, h, i, j):
        return (b, h // g, 0, jnp.minimum(j, i) if causal else j)

    q_spec = pl.BlockSpec((None, None, hd, block),
                          lambda b, h, i, j: (b, h, 0, i))
    kv_spec = pl.BlockSpec((None, None, hd, block), kv_map)
    row_spec = pl.BlockSpec((None, None, 1, block),
                            lambda b, h, i, j: (b, h, 0, i))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, one_pass=one_pass),
        grid=(B, H, n, n),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[_out(q.shape, q.dtype, q),
                   _out((B, H, 1, S), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((1, block), jnp.float32),
                        pltpu.VMEM((1, block), jnp.float32),
                        pltpu.VMEM((hd, block), jnp.float32)],
        compiler_params=_params(("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------- backward --

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *, causal,
                one_pass):
    j, i = pl.program_id(2), pl.program_id(3)
    n = pl.num_programs(3)
    bq = q_ref.shape[1]

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    @pl.when(i == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def step(masked):
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        s = _dot(k, q, _TN, one_pass)                        # (bk, bq)
        if masked:
            s = _diagonal_mask(s)
        p = jnp.exp(s - lse_ref[...])
        dv_sc[...] += _dot(do, p, _NT, one_pass)             # (hd, bk)
        ds = p * (_dot(v_ref[...], do, _TN, one_pass) - delta_ref[...])
        dk_sc[...] += _dot(q, ds, _NT, one_pass)             # (hd, bk)
        dq_sc[i] += _dot(k, ds, _NN, one_pass)               # (hd, bq)

    _steps(step, causal, i, j)

    @pl.when(i == n - 1)
    def _done_dkv():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when((j == n - 1) & (i == n - 1))
    def _done_dq():
        for c in range(dq_sc.shape[0]):
            dq_ref[:, c * bq:(c + 1) * bq] = dq_sc[c].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _attn_bwd_pallas(q, k, v, do, lse, delta, causal, block, one_pass,
                     interpret):
    """(dq, dk, dv) for q/do (B, H, hd, S), k/v (B, Hkv, hd, S), lse/delta
    (B, H, 1, S); dk and dv per query head, (B, H, hd, S)."""
    B, H, hd, S = q.shape
    g = H // k.shape[1]
    n = S // block

    def q_map(b, h, j, i):
        return (b, h, 0, jnp.maximum(i, j) if causal else i)

    q_spec = pl.BlockSpec((None, None, hd, block), q_map)
    row_spec = pl.BlockSpec((None, None, 1, block), q_map)
    kv_spec = pl.BlockSpec((None, None, hd, block),
                           lambda b, h, j, i: (b, h // g, 0, j))
    dkv_spec = pl.BlockSpec((None, None, hd, block),
                            lambda b, h, j, i: (b, h, 0, j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, one_pass=one_pass),
        grid=(B, H, n, n),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((None, None, hd, S),
                                lambda b, h, j, i: (b, h, 0, 0)),
                   dkv_spec, dkv_spec],
        out_shape=[_out(q.shape, q.dtype, q),
                   _out(q.shape, k.dtype, q),
                   _out(q.shape, v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((n, hd, block), jnp.float32),
                        pltpu.VMEM((hd, block), jnp.float32),
                        pltpu.VMEM((hd, block), jnp.float32)],
        compiler_params=_params(("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------- differentiable --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block, one_pass, interpret):
    return _attn_fwd_pallas(q, k, v, causal, block, one_pass, interpret)[0]


def _flash_fwd(q, k, v, causal, block, one_pass, interpret):
    o, lse = _attn_fwd_pallas(q, k, v, causal, block, one_pass, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block, one_pass, interpret, res, do):
    q, k, v, o, lse = res
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=2, keepdims=True)
    dq, dk, dv = _attn_bwd_pallas(q, k, v, do, lse, delta, causal, block,
                                  one_pass, interpret)
    B, Hkv, hd, S = k.shape
    if dk.shape[1] != Hkv:          # GQA: sum each group's query heads
        dk = dk.reshape(B, Hkv, -1, hd, S).sum(2)
        dv = dv.reshape(B, Hkv, -1, hd, S).sum(2)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _attention(q, k, v, causal, interpret):
    S, hd = q.shape[1], q.shape[3]
    one_pass = jax.config.jax_default_matmul_precision in _ONE_PASS

    def seq_minor(x):               # (B, S, H, hd) -> (B, H, hd, S)
        return x.transpose(0, 2, 3, 1)

    o = _flash(seq_minor(q * hd ** -0.5), seq_minor(k), seq_minor(v),
               causal, block_size(S, hd), one_pass, interpret)
    return o.transpose(0, 3, 1, 2)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
              interpret: bool = False) -> jax.Array:
    """``sdpa(q, k, v, causal mask or None)`` for q (B, S, H, hd) and k/v
    (B, S, Hkv, hd), blocked.  The caller checks ``block_size`` first."""
    return _attention(q, k, v, bool(causal), bool(interpret))
