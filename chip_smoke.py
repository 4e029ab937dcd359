"""Smoke run of the FusionLLM training path on a TPU.

    python chip_smoke.py               # one chip: RAD training + codec kernels
    python chip_smoke.py --four-chips  # four chips: the GPipe shard_map step

One process, phases in order, every failed check fatal.  Without a TPU it
exits non-zero before any phase: there is no CPU fallback.

* training: gpt2-xl at its published widths (d_model 1600, 25 heads, d_ff
  6400, vocab 50257), depth cut to 12 of 48 layers — one chip's share of a
  4-stage pipeline.  The RAD step is built by the launcher's own fusion code
  (``repro.launch.train.fusion_job``): OP-Fence schedule on testbed 1,
  AdaTopK at ratio 100, Adam, f32, batch 4 x 1024 tokens (halved while the
  compiled step does not fit the device).  Checks: the uncompressed step-0
  loss and gradients against ``single_device_loss_and_grad`` on the same
  params and batch, five finite AdaTopK losses, and ``tpu_custom_call`` in
  the compiled step (the codec ran as a Pallas kernel).
* codec: ``encode_topk`` / ``decode_topk`` / ``ef_encode_topk`` compiled at
  the training boundary shape in f32 and bf16, and at each edge the step
  compressed (its shape and planned ratio, f32), equal to the
  ``repro.kernels.ref`` oracles.
* ``--four-chips`` (only this phase): the GPipe ``shard_map`` pipeline on a
  ("pod", "model") = (2, 2) mesh at gpt2-xl widths, params placed per stage.
  Checks: the uncompressed pipeline loss against ``causal_lm.train_loss`` on
  one chip, finite gradients through the compressed pod-crossing edge, and
  the codec as the pipeline runs it (``boundary_compress`` inside the
  ``check_vma`` ``shard_map``, forward and backward, at the pod edge's shape
  and ratio) equal to the ``repro.kernels.ref`` oracle on every stage.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: |RAD - single-device| / |single-device| allowed for the uncompressed
#: step-0 loss, and for the pipeline loss against one chip: both sides run
#: the same f32 graph, fused differently.  Measured on TPU v5e: 3.6e-6 and
#: 4.4e-6 (RAD, 12 layers) and 6.0e-7 (pipeline, 48 layers); a wrong stage
#: order, a lost boundary or a dropped micro-batch moves the ~11.1 loss by
#: far more.
LOSS_RTOL = 1e-4
#: worst parameter leaf's ||g_RAD - g_ref|| / ||g_ref|| allowed for the
#: uncompressed step-0 gradients.  Measured 0.0 on TPU v5e and on the CPU:
#: each stage runs the same ops as the monolithic graph, and only the loss's
#: final reduction is fused differently.  A 1% error in one leaf reads 1e-2.
GRAD_RTOL = 1e-4

TRAIN_LAYERS = 12          # one chip's share of gpt2-xl's 48 in 4 stages
TRAIN_BATCH, TRAIN_SEQ = 4, 1024
TRAIN_STEPS = 5
#: the whole of gpt2-xl over 2 x 2 chips, 12 layers each: a compile for a
#: described v5e:2x2 needs 11.0 GB per chip for the value-and-grad
PIPELINE_LAYERS = 48
PIPELINE_MICRO, PIPELINE_MB = 4, 1
RATIO = 100.0              # AdaTopK base ratio (Eq. 7)
TESTBED = 1


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(phase + " " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _bytes_needed(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _bytes_limit():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("bytes_limit")


def _finite_tree(tree) -> bool:
    import jax
    import jax.numpy as jnp
    return all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(tree))


def _worst_leaf_rel_diff(got, want) -> float:
    """max over leaves of ||got - want|| / ||want||."""
    import jax
    import jax.numpy as jnp

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.linalg.norm((a - b).ravel()) / jnp.maximum(
            jnp.linalg.norm(b.ravel()), jnp.finfo(jnp.float32).tiny)

    return float(jax.jit(lambda g, w: jnp.max(jnp.stack(
        jax.tree_util.tree_leaves(jax.tree_util.tree_map(rel, g, w)))))(
            got, want))


def gpt2_xl(n_layers: int):
    from repro.configs import resolve
    return resolve("gpt2-xl").full.replace(n_layers=n_layers)


def training_phase(cfg, *, batch: int, seq: int, steps: int = TRAIN_STEPS
                   ) -> dict:
    """RAD training through ``fusion_job``: AdaTopK steps, then the
    uncompressed step 0's loss and gradients against the single-device
    reference."""
    import jax
    from repro.core.rad import single_device_loss_and_grad
    from repro.data import SyntheticLM
    from repro.launch.train import device_batch, fusion_job, train_fusion
    from repro.optim import adamw, linear_warmup_cosine

    ds = SyntheticLM(vocab=cfg.vocab, seq_len=seq, seed=0, order=1)
    opt = adamw(linear_warmup_cosine(3e-3, 10, steps), weight_decay=0.0)
    limit = _bytes_limit()
    while True:
        job = fusion_job(cfg, opt, batch=batch, seq=seq, compress="adatopk",
                         ratio=RATIO, testbed=TESTBED)
        t0 = time.perf_counter()
        compiled = job.step.lower(job.params, job.opt_state,
                                  device_batch(ds, batch, 0)).compile()
        compile_s = time.perf_counter() - t0
        need = _bytes_needed(compiled)
        if limit is None or need <= limit or batch == 1:
            break
        report("train", batch=batch, bytes_needed=need, bytes_limit=limit,
               action=f"does not fit: halving the batch to {batch // 2}")
        del job, compiled
        gc.collect()
        batch //= 2
    edges = {f"{p}->{c}": (tuple(job.prof[p].out_shape), r)
             for (p, c), r in job.plan.as_mapping().items() if r > 1.0}
    report("train", stages=job.n_stages, batch=batch, seq=seq,
           compressed_edges=json.dumps(edges), compile_s=f"{compile_s:.1f}",
           bytes_needed=need, bytes_limit=limit)
    kernel_in_step = "tpu_custom_call" in compiled.as_text()
    job.step = compiled
    t0 = time.perf_counter()
    losses = train_fusion(job, ds, steps)
    report("train", adatopk_losses=json.dumps(losses),
           tpu_custom_call=kernel_in_step,
           steps_wall_s=f"{time.perf_counter() - t0:.3f}")
    check(all(math.isfinite(v) for v in losses),
          f"non-finite AdaTopK loss: {losses}")
    del job, compiled
    gc.collect()

    job = fusion_job(cfg, opt, batch=batch, seq=seq, compress="none",
                     testbed=TESTBED)
    b0 = device_batch(ds, batch, 0)
    # two gradient trees live at once: the Adam state waits outside HBM
    job.opt_state = None
    gc.collect()
    ref, ref_grads = jax.jit(functools.partial(
        single_device_loss_and_grad, job.graph))(job.params, b0)
    ref = float(ref)
    _, rad_grads = jax.jit(job.loss_and_grad)(job.params, b0)
    grad_rel = _worst_leaf_rel_diff(rad_grads, ref_grads)
    del ref_grads, rad_grads
    gc.collect()
    job.opt_state = opt.init(job.params)
    loss0 = train_fusion(job, ds, 1)[0]
    rel = abs(loss0 - ref) / abs(ref)
    t0 = time.perf_counter()
    train_fusion(job, ds, 1)
    report("train", rad_step0_loss=loss0, single_device_loss=ref,
           rel_diff=rel, tolerance=LOSS_RTOL, grad_worst_leaf_rel=grad_rel,
           grad_tolerance=GRAD_RTOL,
           uncompressed_step_wall_s=f"{time.perf_counter() - t0:.3f}")
    check(rel <= LOSS_RTOL,
          f"uncompressed RAD loss {loss0} vs single-device {ref}")
    check(grad_rel <= GRAD_RTOL,
          f"uncompressed RAD gradients vs single-device: {grad_rel}")
    stages = job.n_stages
    del job
    gc.collect()
    return {"batch": batch, "stages": stages, "edges": edges,
            "losses": losses, "rad_loss": loss0, "ref_loss": ref,
            "rel_diff": rel, "grad_rel_diff": grad_rel,
            "tpu_custom_call": kernel_in_step}


def codec_phase(shape, *, ratio: float = RATIO,
                dtypes=("float32", "bfloat16")) -> dict:
    """Codec kernels (platform-chosen: compiled on a TPU) vs the oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.compression import ratio_to_k
    from repro.kernels import ops, ref

    n = int(np.prod(shape))
    block = ops.tk.DEFAULT_BLOCK
    kpb = ops.per_block_k(n, ratio_to_k(n, ratio), block)
    enc_ref = jax.jit(ref.encode_topk_ref, static_argnums=(1, 2))
    dec_ref = jax.jit(ref.decode_topk_ref, static_argnums=(2,))
    ef_ref = jax.jit(ref.ef_encode_topk_ref, static_argnums=(2, 3))
    kx, kr = jax.random.split(jax.random.PRNGKey(0))
    out = {}
    for name in dtypes:
        dtype = jnp.dtype(name)
        x = jax.random.normal(kx, shape, dtype)
        r = (0.1 * jax.random.normal(kr, shape)).astype(dtype)
        v, m = ops.encode_topk(x, kpb, block)
        v_r, m_r = enc_ref(x, kpb, block)
        dense = ops.decode_topk(v, m, shape)
        ve, me, nr = ops.ef_encode_topk(x, r, kpb, block)
        ve_r, me_r, nr_r = ef_ref(x, r, kpb, block)
        same = {
            "encode": bool(jnp.array_equal(v, v_r) & jnp.array_equal(m, m_r)),
            "decode": bool(jnp.array_equal(dense, dec_ref(v_r, m_r, shape))),
            "ef_encode": bool(jnp.array_equal(ve, ve_r)
                              & jnp.array_equal(me, me_r)
                              & jnp.array_equal(nr, nr_r)),
        }
        report("codec", dtype=name, shape=list(shape), k_per_block=kpb,
               **{f"{k}_equal": v for k, v in same.items()})
        check(all(same.values()), f"codec parity failed for {name}: {same}")
        out[name] = same
    return out


def sharded_codec(mesh, k: int):
    """``boundary_compress`` as the GPipe step runs it: inside
    ``jax.shard_map`` over the stage axes with ``check_vma`` on, every stage
    compressing its own boundary ``x`` forward and its own cotangent ``g``
    backward (``"auto"`` policy).  Arguments and results are (stages, ...)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.core.compression import boundary_compress
    from repro.distributed.pipeline import stage_axes

    spec = P(stage_axes(mesh))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec), check_vma=True)
    def run(x, g):
        y, vjp = jax.vjp(lambda v: boundary_compress(v, k, k, "auto"), x[0])
        return y[None], vjp(g[0])[0][None]

    return run


def pod_edge_codec_phase(mesh, shape) -> bool:
    """The pipeline's codec at its pod edge's boundary ``shape`` and ratio:
    every stage's forward and backward equal to the oracle."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.compression import ratio_to_k
    from repro.distributed.pipeline import (n_stages, pod_edge_ratios,
                                            stage_axes)
    from repro.kernels import ops, ref

    n = int(np.prod(shape))
    ratio = float(max(pod_edge_ratios(mesh, RATIO)))
    k = ratio_to_k(n, ratio)
    block = ops.tk.DEFAULT_BLOCK
    kpb = ops.per_block_k(n, k, block)
    ns = n_stages(mesh)
    kx, kg = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (ns,) + tuple(shape))
    g = jax.random.normal(kg, (ns,) + tuple(shape))
    with jax.set_mesh(mesh):
        stages = NamedSharding(mesh, P(stage_axes(mesh)))
        fwd, bwd = jax.jit(sharded_codec(mesh, k))(
            jax.device_put(x, stages), jax.device_put(g, stages))
        fwd, bwd = np.asarray(fwd), np.asarray(bwd)
    oracle = jax.jit(lambda v: ref.decode_topk_ref(
        *ref.encode_topk_ref(v, kpb, block), shape))
    same = [bool(np.array_equal(fwd[i], np.asarray(oracle(x[i])))
                 and np.array_equal(bwd[i], np.asarray(oracle(g[i]))))
            for i in range(ns)]
    report("pipeline", codec_shape=list(shape), ratio=ratio, k_per_block=kpb,
           sharded_codec_equal=json.dumps(same))
    check(all(same), f"codec in the pipeline's shard_map vs oracle: {same}")
    return all(same)


def pipeline_phase(cfg, mesh) -> dict:
    """GPipe shard_map step on ``mesh``: uncompressed loss vs one chip, and
    finite gradients through the compressed pod-crossing boundary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.distributed.pipeline import (make_pipeline_train_fn,
                                            microbatch, place_params,
                                            pod_edge_ratios)
    from repro.models import causal_lm

    n_micro, seq = PIPELINE_MICRO, TRAIN_SEQ
    kt, kl = jax.random.split(jax.random.PRNGKey(1))
    B = n_micro * PIPELINE_MB
    batch = {"tokens": jax.random.randint(kt, (B, seq), 0, cfg.vocab),
             "labels": jax.random.randint(kl, (B, seq), 0, cfg.vocab)}
    with jax.set_mesh(mesh):
        params = place_params(cfg, mesh, jax.random.PRNGKey(0))
        mbatch = microbatch(batch, n_micro)
        dense = jax.jit(make_pipeline_train_fn(cfg, mesh, n_micro, 1.0))
        t0 = time.perf_counter()
        loss = float(dense(params, mbatch))
        dense_s = time.perf_counter() - t0
        comp = make_pipeline_train_fn(cfg, mesh, n_micro, RATIO,
                                      use_kernel="auto")
        t0 = time.perf_counter()
        loss_c, grads = jax.jit(jax.value_and_grad(comp))(params, mbatch)
        finite = _finite_tree(grads)
        grad_s = time.perf_counter() - t0
        gnorm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                   for g in jax.tree_util.tree_leaves(grads))))
        shards = {d.id: 0 for d in mesh.devices.flat}
        for leaf in jax.tree_util.tree_leaves(params):
            for s in leaf.addressable_shards:
                shards[s.device.id] += s.data.nbytes
        del grads
    report("pipeline", mesh=dict(mesh.shape), layers=cfg.n_layers,
           n_micro=n_micro, microbatch=PIPELINE_MB, seq=seq,
           edge_ratios=[float(r) for r in pod_edge_ratios(mesh, RATIO)],
           param_bytes_per_device=json.dumps(shards))
    one = SingleDeviceSharding(jax.devices()[0])
    ref_params = jax.device_put(params, one)
    del params
    gc.collect()
    ref = float(jax.jit(lambda p, b: causal_lm.train_loss(cfg, p, b)[0])(
        ref_params, jax.device_put(batch, one)))
    del ref_params
    rel = abs(loss - ref) / abs(ref)
    report("pipeline", dense_loss=loss, one_chip_loss=ref, rel_diff=rel,
           tolerance=LOSS_RTOL, compressed_loss=float(loss_c),
           grad_norm=gnorm, grads_finite=finite,
           first_call_s=f"{dense_s:.1f}", grad_first_call_s=f"{grad_s:.1f}")
    check(rel <= LOSS_RTOL, f"pipeline loss {loss} vs one chip {ref}")
    check(finite and math.isfinite(gnorm) and gnorm > 0,
          f"gradients through the compressed pipeline: norm {gnorm}")
    return {"loss": loss, "ref_loss": ref, "rel_diff": rel,
            "grad_norm": gnorm}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the GPipe shard_map phase on 4 chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    report("device", platform=devices[0].platform,
           kind=json.dumps(devices[0].device_kind), count=len(devices),
           compile_cache=enable_compile_cache())

    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 chips, found "
                                 f"{len(devices)}")
        import numpy as np
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("pod", "model"))
        cfg = gpt2_xl(PIPELINE_LAYERS)
        report("config", arch="gpt2-xl", d_model=cfg.d_model,
               n_heads=cfg.n_heads, d_ff=cfg.d_ff, vocab=cfg.vocab,
               n_layers=f"{cfg.n_layers}/48", dtype="float32")
        pipeline_phase(cfg, mesh)
        pod_edge_codec_phase(mesh, (PIPELINE_MB, TRAIN_SEQ, cfg.d_model))
    else:
        cfg = gpt2_xl(TRAIN_LAYERS)
        report("config", arch="gpt2-xl", d_model=cfg.d_model,
               n_heads=cfg.n_heads, d_ff=cfg.d_ff, vocab=cfg.vocab,
               n_layers=f"{cfg.n_layers}/48", dtype="float32",
               cut="depth 48->12 (one chip's share of a 4-stage pipeline)")
        train = training_phase(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ)
        check(train["tpu_custom_call"],
              "no tpu_custom_call in the compiled training step")
        if train["batch"] != TRAIN_BATCH:
            report("config", cut=f"batch {TRAIN_BATCH}->{train['batch']}")
        codec_phase((train["batch"], TRAIN_SEQ, cfg.d_model))
        # the edges the step compressed, at their planned ratios (f32, as
        # trained): the logits edge keeps ~12x more per block than above
        for shape, ratio in train["edges"].values():
            codec_phase(shape, ratio=ratio, dtypes=("float32",))
    stats = devices[0].memory_stats() or {}
    report("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
