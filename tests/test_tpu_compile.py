"""The codec kernels compiled (not interpreted) for a described TPU v5e at
the real boundary size — gpt2-xl, batch 4 x 1024 tokens x d_model 1600 —
in f32 and bf16, the wire kernels also at the logits edge's own size
(4 x 1024 x 50432 padded vocabulary, f32, 484 of every 4096 kept), and
the codec as the four-chip GPipe step runs it (inside the ``check_vma``
``shard_map`` over a described 2 x 2 mesh, at the pod edge's micro-batch
boundary), and the blocked attention kernel inside the RAD and GPipe
steps.  Nothing runs: the chip's compiler must accept each program,
and the HLO must carry the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture (one process may hold
the TPU library at a time, so nothing here touches it at import)."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from helpers import load_chip_smoke
from repro.kernels import ops
from repro.kernels import topk_compress as tk

SHAPE = (4, 1024, 1600)
KPB = 41                       # ratio 100: ceil(ceil(n / 100) / blocks)
LOGITS = (4, 1024, 50432)      # the RAD step's compressed logits edge
LOGITS_KPB = 484


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel(name, shape, kpb):
    return {
        "blockwise_topk_mask": lambda x: tk.blockwise_topk_mask(
            x, kpb, interpret=False),
        "ef_topk": lambda x, r: tk.ef_topk(x, r, kpb, interpret=False),
        "encode_topk": lambda x: tk.encode_topk(x, kpb, interpret=False),
        "ef_encode_topk": lambda x, r: tk.ef_encode_topk(
            x, r, kpb, interpret=False),
        "decode_topk": lambda v, m: tk.decode_topk(v, m, shape,
                                                   interpret=False),
    }[name]


def _args(name, dtype, shape, kpb, sharding):
    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=sharding)
    if name == "decode_topk":
        nb = -(-int(np.prod(shape)) // tk.DEFAULT_BLOCK)
        return (sds((nb, kpb), dtype),
                sds((nb, tk.DEFAULT_BLOCK // 32), jnp.uint32))
    x = sds(shape, dtype)
    return (x, x) if name.startswith("ef_") else (x,)


KERNEL_CASES = [
    pytest.param(name, dtype, SHAPE, KPB, id=f"{name}-{tag}")
    for name in ("blockwise_topk_mask", "ef_topk", "encode_topk",
                 "ef_encode_topk", "decode_topk")
    for dtype, tag in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16"))
] + [pytest.param(name, jnp.float32, LOGITS, LOGITS_KPB, id=f"{name}-logits")
     for name in ("encode_topk", "decode_topk")]


@pytest.mark.parametrize("name,dtype,shape,kpb", KERNEL_CASES)
def test_kernel_compiles_for_v5e(one_chip, name, dtype, shape, kpb):
    compiled = jax.jit(_kernel(name, shape, kpb)).lower(
        *_args(name, dtype, shape, kpb, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pipeline_codec_compiles_for_v5e_2x2(topo, monkeypatch):
    """``chip_smoke.sharded_codec`` at the pod edge (4 micro-batches of
    1 x 1024 x 1600, ratio 300), the policy steered to the compiled kernels
    as it resolves on the chip."""
    monkeypatch.setattr(ops, "off_tpu", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "model"))
    x = jax.ShapeDtypeStruct((4, 1, 1024, 1600), jnp.float32,
                             sharding=NamedSharding(mesh, P(("pod", "model"))))
    k = -(-1024 * 1600 // 300)
    compiled = jax.jit(load_chip_smoke().sharded_codec(mesh, k)).lower(
        x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def v5e_step(one_chip):
    """The RAD AdaTopK step at smoke widths compiled for the chip with the
    codec's kernels: (job, optimized HLO text, its scope table as the
    benchmark reads it)."""
    import unittest.mock
    from chipbench.scope_reduce import instruction_scopes
    from repro.configs import resolve
    from repro.launch.train import fusion_job
    from repro.obs.scopes import classify
    from repro.optim import adamw

    with unittest.mock.patch.object(ops, "off_tpu", lambda: False):
        job = fusion_job(resolve("gpt2-xl").smoke, adamw(1e-3), batch=2,
                         seq=64, compress="adatopk")

        def sds(tree):
            return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32,
                                         sharding=one_chip)
                 for k in ("tokens", "labels")}
        hlo = job.step.lower(sds(job.params), sds(job.opt_state),
                             batch).compile().as_text()
    return job, hlo, instruction_scopes(hlo, classify)


def test_rad_step_codec_kernels_fall_in_their_edge_scopes(v5e_step):
    """Each kernel instruction (``_encode_pallas.N``, ``_decode_pallas.N``,
    which ``codec_ms_per_step`` reads) falls in the codec scope of a
    compressed edge, in both directions of each."""
    from repro.obs import scopes as S
    job, _, table = v5e_step
    kernels = {n: s for n, s in table.items()
               if n.startswith(("_encode_pallas", "_decode_pallas"))}
    assert kernels and all(s is not None and s.kind == S.CODEC
                           for s in kernels.values())
    planned = {p for (p, _), r in job.plan.as_mapping().items() if r > 1.0}
    assert {(s.where.split("/")[0], s.direction)
            for s in kernels.values()} == {(p, d) for p in planned
                                           for d in S.DIRECTIONS}


def test_rad_step_matmuls_stay_in_their_stage_scopes(v5e_step):
    """The chip's compiler fuses the optimizer's update of a weight into
    the matmul that computes the weight's gradient.  Every matmul still
    counts under the stage scope its own op_name names, so that no stage
    can read less time than its FLOPs need at the chip's peak."""
    from chipbench.scope_reduce import _CALLS, _computations
    from repro.obs.scopes import OPTIM, classify
    _, hlo, table = v5e_step
    comps = _computations(hlo)

    def matmuls(text):
        found = []
        if re.search(r"\s(?:dot|convolution)\(", text):
            op = re.search(r'op_name="([^"]*)"', text)
            found.append(classify(op.group(1)) if op else None)
        for c in _CALLS.findall(text):
            for _, t in comps[c]:
                found += matmuls(t)
        return found

    entry = next(c for c in comps if c.startswith("main"))
    fused_with_optim = 0
    for name, text in comps[entry]:
        for scope in matmuls(text):
            if scope is not None:
                assert table[name] == scope, (name, scope, table[name])
        body = "\n".join(t for c in _CALLS.findall(text)
                         for _, t in comps[c])
        if matmuls(text) and f"/{OPTIM}/" in body:
            fused_with_optim += 1
    assert fused_with_optim      # what makes the rule matter


#: smoke widths with gpt2-xl's head size and a sequence of three 128
#: blocks, so that every block takes the attention kernel
ATTN_WIDTHS = dict(n_heads=2, n_kv_heads=2, head_dim=64)
ATTN_SEQ = 384
ATTN_CALLS = re.compile(r"%(_attn_(?:fwd|bwd)_pallas)[.\d]* = .*tpu_custom_call")


def _lowered_attention_calls(text):
    """``tpu_custom_call``s inside the attention kernel's functions of a
    lowered (StableHLO) module."""
    return sum(f.count("tpu_custom_call")
               for f in text.split("func.func")[1:]
               if f.split("(", 1)[0].strip().split("@")[-1].startswith(
                   "_attn_"))


def _rad_dense_step(one_chip, layers):
    """The RAD step without compression at ``layers`` blocks, traced for the
    chip: (lowered module text, compiled HLO text)."""
    import unittest.mock
    from repro.configs import resolve
    from repro.launch.train import fusion_job
    from repro.optim import adamw

    cfg = resolve("gpt2-xl").smoke.replace(n_layers=layers, **ATTN_WIDTHS)
    with unittest.mock.patch.object(ops, "off_tpu", lambda: False):
        job = fusion_job(cfg, adamw(1e-3), batch=2, seq=ATTN_SEQ,
                         compress="none")

        def sds(tree):
            return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        batch = {k: jax.ShapeDtypeStruct((2, ATTN_SEQ), jnp.int32,
                                         sharding=one_chip)
                 for k in ("tokens", "labels")}
        lowered = job.step.lower(sds(job.params), sds(job.opt_state), batch)
        return lowered.as_text(), lowered.compile().as_text()


def test_rad_step_lowers_the_attention_kernel_once_at_any_depth(one_chip):
    """Every block of the RAD step runs the attention kernel, and the
    program lowers it once per direction whatever its depth: the module
    holds the same two kernel calls at 2 and at 4 layers (a kernel traced
    per layer adds a call per layer, and set-up time with it).  The chip's
    compiler inlines them into one forward and one backward call a layer,
    each inside its stage's scope."""
    from chipbench.scope_reduce import instruction_scopes
    from repro.obs import scopes as S

    lowered = {}
    for layers in (2, 4):
        text, hlo = _rad_dense_step(one_chip, layers)
        lowered[layers] = _lowered_attention_calls(text)
        calls = ATTN_CALLS.findall(hlo)
        assert sorted(set(calls)) == ["_attn_bwd_pallas", "_attn_fwd_pallas"]
        assert len(calls) == 2 * layers
        table = instruction_scopes(hlo, S.classify)
        kernels = {n: s for n, s in table.items() if n.startswith("_attn_")}
        assert {(s.kind, s.direction) for s in kernels.values()} == {
            (S.STAGE, "fwd"), (S.STAGE, "bwd")}
    assert lowered[2] == lowered[4] == 2


@pytest.fixture(scope="module")
def v5e_pipeline_step(topo):
    """The GPipe step (smoke widths with gpt2-xl's head size, 4 layers over
    a described 2 x 2 mesh, Adam, the pod edge compressed by the codec's
    kernels, every block on the attention kernel) compiled for the chip:
    its optimized HLO text."""
    import unittest.mock
    from repro.configs import resolve
    from repro.distributed.pipeline import (make_pipeline_train_step,
                                            stage_axes)
    from repro.models import causal_lm
    from repro.optim import adamw

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "model"))
    cfg = resolve("gpt2-xl").smoke.replace(n_layers=4, max_seq=ATTN_SEQ,
                                           **ATTN_WIDTHS)
    opt = adamw(1e-3)
    shapes = jax.eval_shape(lambda: causal_lm.init(cfg,
                                                   jax.random.PRNGKey(0)))
    rep = NamedSharding(mesh, P())

    def placed(tree, key):
        sh = NamedSharding(mesh, P(stage_axes(mesh))) if key == "blocks" \
            else rep
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree)

    params = {k: placed(v, k) for k, v in shapes.items()}
    state = jax.eval_shape(opt.init, shapes)
    state = state._replace(
        step=jax.ShapeDtypeStruct((), state.step.dtype, sharding=rep),
        inner={"m": params, "v": params})
    batch = {k: jax.ShapeDtypeStruct((4, 1, ATTN_SEQ), jnp.int32,
                                     sharding=rep)
             for k in ("tokens", "labels")}
    with unittest.mock.patch.object(ops, "off_tpu", lambda: False), \
            jax.set_mesh(mesh):
        step = make_pipeline_train_step(cfg, mesh, opt, 4, 100.0,
                                        use_kernel="auto")
        return jax.jit(step).lower(params, state, batch).compile().as_text()


def test_pipeline_step_scopes_on_v5e_2x2(v5e_pipeline_step):
    """The GPipe step compiled for the chip: as the benchmark reads the
    chip's fusions, every matmul falls in `pipe/blocks` or `pipe/head` and
    every codec kernel call in the edge's scope, in both directions."""
    from chipbench.scope_reduce import _CALLS, _computations, \
        instruction_scopes
    from repro.obs.scopes import classify

    hlo = v5e_pipeline_step
    table = instruction_scopes(hlo, classify)
    kernels = {n: s for n, s in table.items()
               if n.startswith(("_encode_pallas", "_decode_pallas"))}
    assert kernels and {str(s) for s in kernels.values()} == {
        "pipe/edge/block_1/fwd", "pipe/edge/block_1/bwd"}
    comps = _computations(hlo)

    def holds_matmul(text):
        return bool(re.search(r"\s(?:dot|convolution)\(", text)) or any(
            holds_matmul(t) for c in _CALLS.findall(text)
            for _, t in comps.get(c, ()))

    fused = {c for body in comps.values() for _, t in body
             if re.search(r"\bfusion\(", t) for c in _CALLS.findall(t)}
    matmuls = {str(table[n]) for comp, body in comps.items()
               if comp not in fused for n, t in body if holds_matmul(t)}
    assert matmuls == {"pipe/blocks/fwd", "pipe/blocks/bwd",
                       "pipe/head/fwd", "pipe/head/bwd"}


def test_pipeline_blocks_take_the_attention_kernel_on_v5e_2x2(
        v5e_pipeline_step):
    """Inside the ``check_vma`` ``shard_map``, in the rematerialized scan
    over a stage's blocks, the attention kernel compiles: its forward (run
    twice, once recomputed under ``jax.checkpoint``) and its backward, each
    in `pipe/blocks`."""
    from chipbench.scope_reduce import instruction_scopes
    from repro.obs.scopes import classify

    hlo = v5e_pipeline_step
    assert set(ATTN_CALLS.findall(hlo)) == {"_attn_fwd_pallas",
                                            "_attn_bwd_pallas"}
    table = instruction_scopes(hlo, classify)
    assert {str(s) for n, s in table.items() if n.startswith("_attn_")} == {
        "pipe/blocks/fwd", "pipe/blocks/bwd"}
