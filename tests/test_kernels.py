"""Pallas Top-K kernels vs the pure-jnp oracle: shape/dtype/k sweeps in
interpret mode (deliverable c — per-kernel allclose), plus the fused
wire-encode/decode round trip and the kernel dispatch policy.

Property tests run only when hypothesis is installed; the parametrized
parity sweeps always run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels import topk_compress as tk


SHAPES = [(64,), (4096,), (5000,), (32, 257), (8, 128, 17), (3, 5, 7, 11)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]
RATIOS = [2, 10, 100]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("ratio", RATIOS)
def test_blockwise_topk_exact_vs_oracle(shape, dtype, ratio):
    rng = np.random.default_rng(hash((shape, ratio)) % 2**32)
    x = jnp.asarray(rng.standard_normal(shape), dtype=dtype)
    n = int(np.prod(shape))
    block = 512
    kpb = max(1, (n // ratio) // max(1, -(-n // block)) or 1)
    got = tk.blockwise_topk_mask(x, kpb, block=block, interpret=True)
    want = ref.blockwise_topk_mask_ref(x, kpb, block=block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_threshold_search_exact_at_duplicates():
    x = jnp.asarray([1.0, -1.0, 1.0, 0.5, -0.25, 1.0, 0.0, 0.1], jnp.float32)
    got = tk.blockwise_topk_mask(x, 2, block=8, interpret=True)
    # threshold = 1.0; ties keep all three 1.0-magnitude entries
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray([1.0, -1.0, 1.0, 0, 0, 1.0, 0, 0],
                                    dtype=np.float32))


def test_ef_topk_fused_matches_reference():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(3000), jnp.float32)
    r = jnp.asarray(rng.standard_normal(3000) * 0.1, jnp.float32)
    s1, nr1 = tk.ef_topk(x, r, 8, block=512, interpret=True)
    s2, nr2 = ref.ef_topk_ref(x, r, 8, block=512)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_allclose(np.asarray(nr1), np.asarray(nr2), atol=1e-6)


def test_jit_wrappers():
    x = jnp.asarray(np.random.default_rng(4).standard_normal(2048),
                    jnp.float32)
    y = ops.topk_mask(x, 100)
    assert 100 <= int(np.sum(np.asarray(y) != 0)) <= 120
    y2 = ops.blockwise_topk_mask(x, 16, block=256)
    assert int(np.sum(np.asarray(y2) != 0)) == 16 * 8


def test_zero_input_keeps_everything_zero():
    x = jnp.zeros(1024, jnp.float32)
    y = tk.blockwise_topk_mask(x, 4, block=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.zeros(1024))


# ------------------------------------------------- fused encode / decode --

ENC_CASES = [((4096,), 11), ((5000,), 13), ((33, 257), 17), ((64,), 9)]
# (shape, k per block, block) at the wire kernels' grouping of 128 blocks
# per grid step, one per lane: fewer blocks than a group, a last group
# padded with zero blocks, k = 1, 14, 484 and the whole block; the input's
# first block is all zeros and its second all ties, next to random ones
GROUP_CASES = [((3 * 4096 + 100,), 484, 4096), ((131 * 512 - 9,), 14, 512),
               ((150 * 32,), 1, 32), ((70 * 32,), 32, 32)]


def _enc_cases(blocks):
    """ENC_CASES over ``blocks`` (random inputs), then GROUP_CASES (mixed
    inputs): (shape, kpb, blocks, mixed)."""
    return ([pytest.param(s, k, blocks, False, id=f"shape{i}-{k}")
             for i, (s, k) in enumerate(ENC_CASES)]
            + [pytest.param(s, k, (b,), True,
                            id=f"n{int(np.prod(s))}-k{k}-block{b}")
               for s, k, b in GROUP_CASES])


def _enc_input(rng, shape, dtype, block, mixed):
    flat = rng.standard_normal(int(np.prod(shape)))
    if mixed:
        flat[:block] = 0.0
        flat[block:2 * block] = np.where(rng.random(block) < 0.5, -1.5, 1.5)
    return jnp.asarray(flat.reshape(shape), dtype=dtype)


@pytest.mark.parametrize("shape,kpb,blocks,mixed", _enc_cases((32, 512)))
@pytest.mark.parametrize("dtype", DTYPES)
def test_encode_kernel_matches_oracle(shape, kpb, blocks, mixed, dtype):
    rng = np.random.default_rng(hash((shape, kpb)) % 2**32)
    x = _enc_input(rng, shape, dtype, blocks[0], mixed)
    for block in blocks:
        v_k, m_k = tk.encode_topk(x, kpb, block=block, interpret=True)
        v_r, m_r = ref.encode_topk_ref(x, kpb, block=block)
        np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
        np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))


@pytest.mark.parametrize("shape,kpb,blocks,mixed", _enc_cases((512,)))
@pytest.mark.parametrize("dtype", DTYPES)
def test_ef_encode_kernel_matches_oracle(shape, kpb, blocks, mixed, dtype):
    rng = np.random.default_rng(hash((shape, kpb, 1)) % 2**32)
    (block,) = blocks
    x = _enc_input(rng, shape, dtype, block, mixed)
    r = jnp.asarray(rng.standard_normal(shape) * 0.1, dtype=dtype)
    v_k, m_k, nr_k = tk.ef_encode_topk(x, r, kpb, block=block,
                                       interpret=True)
    v_r, m_r, nr_r = ref.ef_encode_topk_ref(x, r, kpb, block=block)
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(nr_k), np.asarray(nr_r))


@pytest.mark.parametrize("shape,kpb,blocks,mixed", _enc_cases((512,)))
def test_encode_decode_round_trip(shape, kpb, blocks, mixed):
    """decode(encode(x)) reconstructs exactly the kept elements — i.e. the
    tie-capped keep set as a dense tensor — for kernel and oracle alike, in
    every input dtype."""
    (block,) = blocks
    for dtype in DTYPES:
        rng = np.random.default_rng(hash(shape) % 2**32)
        x = _enc_input(rng, shape, dtype, block, mixed)
        v, m = tk.encode_topk(x, kpb, block=block, interpret=True)
        dense_k = tk.decode_topk(v, m, x.shape, interpret=True)
        dense_r = ref.decode_topk_ref(
            *ref.encode_topk_ref(x, kpb, block=block), shape=x.shape)
        np.testing.assert_array_equal(np.asarray(dense_k),
                                      np.asarray(dense_r))
        # every reconstructed nonzero matches the input at its position
        got = np.asarray(dense_k)
        want = np.asarray(x)
        nz = got != 0
        np.testing.assert_array_equal(got[nz], want[nz])


def test_encode_all_zeros_and_ties():
    # all-zeros: exactly kpb slots per block kept (wire capacity), all zero
    x0 = jnp.zeros(256, jnp.float32)
    v, m = tk.encode_topk(x0, 8, block=32, interpret=True)
    assert v.shape == (8, 8)
    np.testing.assert_array_equal(np.asarray(v), np.zeros((8, 8)))
    assert int(np.sum([bin(w).count("1") for w in np.asarray(m).ravel()])) \
        == 8 * 8
    rt = tk.decode_topk(v, m, x0.shape, interpret=True)
    np.testing.assert_array_equal(np.asarray(rt), np.zeros(256))
    # all-ones (every element ties at the threshold): capped at exactly kpb
    x1 = jnp.ones(256, jnp.float32)
    v1, m1 = tk.encode_topk(x1, 7, block=32, interpret=True)
    v1r, m1r = ref.encode_topk_ref(x1, 7, block=32)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v1r))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m1r))
    rt1 = np.asarray(tk.decode_topk(v1, m1, x1.shape, interpret=True))
    assert int(np.sum(rt1 != 0)) == 7 * 8
    # ties keep the *first* k - n_above in index order
    assert np.all(rt1.reshape(8, 32)[:, :7] == 1.0)


def test_encode_capped_vs_mask_superset():
    """The dense kernels keep a tie-superset; the encode kernels cap at the
    wire capacity.  On a tie-heavy tensor the decode output must be a
    subset of the dense mask with exactly kpb survivors per block."""
    x = jnp.asarray(np.repeat([3.0, 1.0], 16), jnp.float32)   # 16-way ties
    mask = np.asarray(tk.blockwise_topk_mask(x, 4, block=32, interpret=True))
    v, m = tk.encode_topk(x, 4, block=32, interpret=True)
    enc = np.asarray(tk.decode_topk(v, m, x.shape, interpret=True))
    assert int(np.sum(mask != 0)) == 16      # superset: all 3.0-ties kept
    assert int(np.sum(enc != 0)) == 4        # capped at wire capacity
    assert np.all(mask[enc != 0] == enc[enc != 0])


def test_keep_capped_is_stable_topk():
    """_keep_capped (the executable spec) agrees with the stable-top_k
    formulation encode_topk_ref ships — including tie-heavy rows."""
    rng = np.random.default_rng(11)
    for row in [rng.standard_normal((4, 64)),
                np.repeat(rng.standard_normal((4, 8)), 8, axis=1),
                np.zeros((4, 64))]:
        tiles = jnp.asarray(row, jnp.float32)
        for k in (1, 5, 63):
            keep = np.asarray(ref._keep_capped(ref._mag_bits(tiles), k))
            idx = np.sort(np.asarray(
                jax.lax.top_k(jnp.abs(tiles), k)[1]), axis=1)
            want = np.zeros(keep.shape, bool)
            np.put_along_axis(want, idx, True, axis=1)
            np.testing.assert_array_equal(keep, want)


# --------------------------------------------------------- dispatch policy --

def test_resolve_policy():
    assert ops.resolve_policy(False) == "global"
    assert ops.resolve_policy(None) == "global"
    assert ops.resolve_policy("off") == "global"
    on_tpu = jax.default_backend() == "tpu"
    assert ops.resolve_policy("auto") == ("pallas" if on_tpu else "xla")
    assert ops.resolve_policy(True) == ("pallas" if on_tpu else "interpret")
    assert ops.resolve_policy("force") == ops.resolve_policy(True)
    with pytest.raises(ValueError):
        ops.resolve_policy("warp-speed")


def test_codec_modes_agree():
    """xla and interpret codec paths are bit-identical (the policy only
    changes where the math runs, never what it computes)."""
    x = jnp.asarray(np.random.default_rng(5).standard_normal(5000),
                    jnp.float32)
    a = ops.codec_topk_mask(x, 50, mode="xla")
    b = ops.codec_topk_mask(x, 50, mode="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    r = jnp.asarray(np.random.default_rng(6).standard_normal(5000) * 0.1,
                    jnp.float32)
    sa, ra = ops.codec_ef_topk(x, r, 50, mode="xla")
    sb, rb = ops.codec_ef_topk(x, r, 50, mode="interpret")
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))


# ------------------------------------------------------- property tests --

if HAVE_HYPOTHESIS:
    @given(st.integers(8, 2000), st.integers(1, 64),
           st.sampled_from([128, 256, 512]))
    @settings(max_examples=25, deadline=None)
    def test_kernel_oracle_property(n, k, block):
        x = jnp.asarray(np.random.default_rng(n * 7 + k).standard_normal(n),
                        jnp.float32)
        got = tk.blockwise_topk_mask(x, k, block=block, interpret=True)
        want = ref.blockwise_topk_mask_ref(x, k, block=block)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(st.integers(8, 2000), st.integers(1, 48),
           st.sampled_from([32, 128, 512]),
           st.sampled_from(["normal", "zeros", "ties"]))
    @settings(max_examples=25, deadline=None)
    def test_encode_round_trip_property(n, k, block, regime):
        rng = np.random.default_rng(n * 13 + k)
        if regime == "zeros":
            x = jnp.zeros(n, jnp.float32)
        elif regime == "ties":
            x = jnp.asarray(rng.integers(0, 3, n).astype(np.float32))
        else:
            x = jnp.asarray(rng.standard_normal(n), jnp.float32)
        v_k, m_k = tk.encode_topk(x, k, block=block, interpret=True)
        v_r, m_r = ref.encode_topk_ref(x, k, block=block)
        np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
        np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
        rt = tk.decode_topk(v_k, m_k, x.shape, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(rt),
            np.asarray(ref.decode_topk_ref(v_r, m_r, x.shape)))
