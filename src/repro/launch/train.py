"""Training launcher.

Two modes (DESIGN.md §4):
* ``gspmd``  — jitted train_step on the local mesh (the production path at
  container scale: 1 CPU device; on a pod the same code sees 256 chips);
* ``fusion`` — the paper's decentralized runtime: OP-Fence schedule over a
  simulated geo cluster, RAD executor with AdaTopK compression; reports the
  REAL loss curve plus the SIMULATED per-iteration wall time on the chosen
  testbed.

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-xl --size smoke \
        --mode fusion --steps 50 --compress adatopk --ratio 100

Reporting goes through :mod:`repro.obs.slog` — ``event k=v`` lines on
stderr honoring ``--log-level``/``--quiet``.

Under a profiler, the RAD step's device ops carry the scopes of
:mod:`repro.obs.scopes` (stage, boundary edge, ``optim``) and
``train_fusion``'s steps show as host spans on the device trace's clock:
``train.step`` holding ``train.batch``, ``train.dispatch`` and
``train.fetch``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

from repro.launch.cache import enable_compile_cache
from repro.obs import slog
from repro.obs.trace import host_span


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-xl")
    ap.add_argument("--size", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--mode", choices=["gspmd", "fusion"], default="gspmd")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--compress", choices=["none", "uniform", "adatopk"],
                    default="none")
    ap.add_argument("--ratio", type=float, default=100.0)
    ap.add_argument("--testbed", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    slog.add_logging_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    log = slog.get_logger("train", level=slog.level_from_args(args))

    from repro.configs import resolve
    from repro.data import SyntheticLM
    from repro.optim import adamw, linear_warmup_cosine
    from repro.checkpoint import save_checkpoint

    entry = resolve(args.arch)
    cfg = entry.smoke if args.size == "smoke" else entry.full
    cfg = cfg.replace(max_seq=max(cfg.max_seq, args.seq))
    ds = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, seed=0)
    opt = adamw(linear_warmup_cosine(args.lr, 10, args.steps),
                weight_decay=0.0)

    if args.mode == "gspmd":
        losses = _train_gspmd(cfg, ds, opt, args, log)
    else:
        job = fusion_job(cfg, opt, batch=args.batch, seq=args.seq,
                         compress=args.compress, ratio=args.ratio,
                         testbed=args.testbed)
        log.event("fusion_plan", testbed=args.testbed, stages=job.n_stages,
                  sim_iteration_s=job.sim.iteration_time,
                  comm_mb=job.sim.comm_bytes / 1e6)
        losses = train_fusion(job, ds, args.steps, log=log,
                              log_every=args.log_every)
    log.event("train_done", mode=args.mode, steps=args.steps,
              final_loss=losses[-1], start_loss=losses[0])


def _train_gspmd(cfg, ds, opt, args, log):
    from repro.distributed.steps import make_train_step
    from repro.models import causal_lm
    from repro.checkpoint import save_checkpoint

    params = causal_lm.init(cfg, jax.random.PRNGKey(0))
    state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt))
    losses = []
    t0 = time.time()
    for i in range(args.steps):
        params, state, metrics = step_fn(params, state,
                                         device_batch(ds, args.batch, i))
        losses.append(float(metrics["loss"]))
        if i % args.log_every == 0:
            log.event("train_step", step=i, loss=losses[-1],
                      s_per_step=(time.time() - t0) / (i + 1))
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1, params,
                            metadata={"arch": cfg.name, "mode": "gspmd"})
    return losses


@dataclasses.dataclass
class FusionJob:
    """One RAD training job on the paper's decentralized runtime: the
    OP-DAG and its per-op profile, the compression plan over its OP-Fence
    schedule, the simulated iteration on the testbed, the RAD loss and
    gradients the step applies, and the jitted step with its current params
    and optimizer state (both donated to the step)."""

    graph: Any
    prof: Any
    plan: Any
    sim: Any
    n_stages: int
    batch: int
    params: Any
    opt_state: Any
    loss_and_grad: Callable
    step: Callable


def fusion_job(cfg, opt, *, batch: int, seq: int, compress: str = "adatopk",
               ratio: float = 100.0, testbed: int = 1) -> FusionJob:
    """Plan (OP-Fence + AdaTopK on the testbed) and build the RAD step.
    Boundary compression runs through the ``"auto"`` kernel policy:
    compiled Pallas codec kernels on a TPU, the fused-XLA oracle with the
    same semantics elsewhere."""
    from repro.core import (network, plan_adatopk, plan_none, plan_uniform,
                            schedule_opfence, simulate_iteration,
                            PipelineProgram, pipeline_loss_and_grad)
    from repro.models.opgraph_models import gpt_opgraph

    graph = gpt_opgraph(cfg, batch, seq)
    shapes = {"tokens": (batch, seq), "labels": (batch, seq)}
    prof = graph.annotate(shapes)
    cluster = network.paper_testbed(testbed, seed=0)
    sch = schedule_opfence(graph, prof, cluster)
    plan = {"none": lambda: plan_none(graph, sch.placement),
            "uniform": lambda: plan_uniform(graph, sch.placement, ratio),
            "adatopk": lambda: plan_adatopk(graph, prof, cluster,
                                            sch.placement, ratio)
            }[compress]()
    sim = simulate_iteration(graph, prof, sch, cluster, plan, n_micro=2)
    prog = PipelineProgram.build(graph, sch.pipeline_subdags(graph))
    params = graph.init(jax.random.PRNGKey(0), shapes)

    def loss_and_grad(params, batch):
        return pipeline_loss_and_grad(prog, params, batch, plan,
                                      use_kernel="auto")

    def step(params, state, batch):
        loss, grads = loss_and_grad(params, batch)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    return FusionJob(graph=graph, prof=prof, plan=plan, sim=sim,
                     n_stages=len(sch.stage_devices()), batch=batch,
                     params=params, opt_state=opt.init(params),
                     loss_and_grad=loss_and_grad, step=jax.jit(step, donate_argnums=(0, 1)))


def device_batch(ds, batch: int, i: int) -> Dict[str, jax.Array]:
    b = ds.batch(batch, i)
    return {"tokens": jnp.asarray(b["tokens"]),
            "labels": jnp.asarray(b["labels"])}


def train_fusion(job: FusionJob, ds, steps: int, log=None,
                 log_every: int = 10) -> List[float]:
    """Run ``steps`` RAD steps; the job keeps the updated params/state.
    Each step is a ``train.step`` host span around its ``train.batch``,
    ``train.dispatch`` and ``train.fetch`` (the loss read back to the
    host)."""
    losses = []
    for i in range(steps):
        with host_span("train.step"):
            with host_span("train.batch"):
                batch = device_batch(ds, job.batch, i)
            with host_span("train.dispatch"):
                job.params, job.opt_state, loss = job.step(
                    job.params, job.opt_state, batch)
            with host_span("train.fetch"):
                losses.append(float(loss))
        if log is not None and i % log_every == 0:
            log.event("train_step", step=i, loss=losses[-1],
                      sim_wall_s=job.sim.iteration_time * (i + 1))
    return losses


if __name__ == "__main__":
    main()
