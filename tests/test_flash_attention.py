"""The blocked attention kernel (``kernels/flash_attention``) against
``sdpa``, its reference, in the Pallas interpreter: forward output and the
gradients of q, k and v, and the rule by which ``attn_train`` takes it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models import attention as attn

# (batch, seq, heads, kv heads, head_dim, causal): one block (seq 128) and
# three (seq 384, blocks of 128), MHA and GQA, head_dim 64 and 128
CASES = [
    pytest.param(2, 128, 2, 2, 64, True, id="one-block-mha-causal"),
    pytest.param(1, 384, 2, 2, 64, True, id="three-blocks-mha-causal"),
    pytest.param(1, 384, 4, 2, 64, True, id="three-blocks-gqa-causal"),
    pytest.param(1, 384, 2, 2, 64, False, id="three-blocks-mha-bidir"),
    pytest.param(1, 128, 4, 1, 64, False, id="one-block-mqa-bidir"),
    pytest.param(1, 256, 2, 1, 128, True, id="hd128-gqa-causal"),
]


def _inputs(B, S, H, Hkv, hd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, H, hd)),
            jax.random.normal(ks[1], (B, S, Hkv, hd)),
            jax.random.normal(ks[2], (B, S, Hkv, hd)),
            jax.random.normal(ks[3], (B, S, H, hd)))


def _both(q, k, v, do, causal):
    """(output, dq, dk, dv) of the kernel and of ``sdpa``."""
    mask = attn.make_mask(q.shape[1], k.shape[1], causal, None)
    kernel = lambda q, k, v: fa.attention(q, k, v, causal, interpret=True)
    ref = lambda q, k, v: attn.sdpa(q, k, v, mask)
    out = []
    for f in (kernel, ref):
        o, vjp = jax.vjp(f, q, k, v)
        out.append((o,) + vjp(do))
    return out


def _rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("B,S,H,Hkv,hd,causal", CASES)
def test_kernel_matches_sdpa(B, S, H, Hkv, hd, causal):
    """With f32 dots on both sides the kernel gives ``sdpa``'s output and
    gradients to f32 rounding: the online softmax only reorders sums."""
    with jax.default_matmul_precision("highest"):
        got, want = _both(*_inputs(B, S, H, Hkv, hd), causal)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel_err(a, b) < 1e-5, name


def test_kernel_at_default_precision_takes_one_bf16_pass():
    """At the default matmul precision the kernel's dots round their
    operands to bf16, as XLA does for ``sdpa``'s einsums on a TPU: the
    result stays within bf16 rounding of the f32 product, and differs
    from it."""
    args = _inputs(1, 256, 2, 2, 64)
    got, _ = _both(*args, True)
    with jax.default_matmul_precision("highest"):
        _, want = _both(*args, True)
    errs = [_rel_err(a, b) for a, b in zip(got, want)]
    assert max(errs) < 2e-2 and min(errs) > 1e-5


def test_block_size_rule():
    assert fa.block_size(1024, 64) == 512
    assert fa.block_size(384, 64) == 128
    assert fa.block_size(768, 128) == 256
    assert fa.block_size(1000, 64) is None          # no 128 multiple
    assert fa.block_size(64, 64) is None
    assert fa.block_size(32768, 64) is None         # dq block too large


def test_one_trace_for_every_call_of_a_shape():
    """Calls with equal shapes reuse the entry point's trace: nothing is
    rebuilt per call, so a program lowers the kernel once."""
    q, k, v, _ = _inputs(1, 128, 2, 2, 64)
    fa.attention(q, k, v, True, interpret=True)
    before = fa._attention._cache_size()
    for _ in range(3):
        fa.attention(q + 1, k, v, True, interpret=True)
    assert fa._attention._cache_size() == before


def _takes_kernel(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as on a TPU (the jaxpr is only traced, never run)."""
    monkeypatch.setattr(ops, "off_tpu", lambda: False)


D, H, HD = 128, 2, 64


@pytest.fixture
def layer():
    return attn.attn_init(jax.random.PRNGKey(1), D, H, H, HD)


def _x(S, B=1):
    return jnp.ones((B, S, D), jnp.float32)


def _train(p, x, **kw):
    return attn.attn_train(p, x, n_heads=H, n_kv=H, head_dim=HD, **kw)


@pytest.mark.parametrize("causal", [True, False])
def test_attn_train_takes_the_kernel_on_a_tpu(on_tpu, layer, causal):
    assert _takes_kernel(lambda p, x: _train(p, x, causal=causal),
                         layer, _x(256))


@pytest.mark.parametrize("case", ["window", "cross", "seq-not-a-block",
                                  "cpu"])
def test_attn_train_falls_back_to_sdpa(monkeypatch, layer, case):
    monkeypatch.setattr(ops, "off_tpu", lambda: case == "cpu")
    kw, x = {}, _x(256)
    if case == "window":
        kw = {"window": 64}
    elif case == "cross":
        kw = {"x_kv": _x(256)}
    elif case == "seq-not-a-block":
        x = _x(200)
    assert not _takes_kernel(lambda p, x: _train(p, x, **kw), layer, x)


def test_prefill_and_decode_keep_sdpa(on_tpu, layer):
    prefill = lambda p, x: attn.attn_prefill(p, x, n_heads=H, n_kv=H,
                                             head_dim=HD, cache_len=256)
    assert not _takes_kernel(prefill, layer, _x(256))
    _, cache = prefill(layer, _x(256))
    decode = lambda p, x, c: attn.attn_decode(p, x, c, jnp.int32(3),
                                              n_heads=H, n_kv=H, head_dim=HD)
    assert not _takes_kernel(decode, layer, _x(1), cache)


def test_kernel_engages_in_a_model_step_and_matches_sdpa(monkeypatch):
    """A whole block (gpt2's LayerNorm, GELU MLP, learned positions) with
    the kernel in place of ``sdpa``, interpreted: loss and gradients
    agree with the ``sdpa`` path."""
    from repro.configs import resolve
    from repro.models import causal_lm

    cfg = resolve("gpt2-xl").smoke.replace(n_layers=1, n_heads=2,
                                           n_kv_heads=2, head_dim=64)
    params = causal_lm.init(cfg, jax.random.PRNGKey(0))
    block = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.d_model))

    def loss(p):
        return jnp.sum(causal_lm._dense_block(cfg, p, x, None) ** 2)

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss)(block)
        real = fa.attention
        monkeypatch.setattr(ops, "off_tpu", lambda: False)
        monkeypatch.setattr(fa, "attention", lambda *a: real(
            *a, interpret=True))
        got = jax.value_and_grad(loss)(block)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        assert _rel_err(a, b) < 1e-4
