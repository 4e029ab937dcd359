"""The GPipe ``shard_map`` training step over a (pod, model) mesh of chips.

``repro.distributed.pipeline.make_pipeline_train_fn`` gives the pipeline's
loss with the traffic's kernel policy and base ratio (the pod-crossing edge
compressed by ``boundary_compress``); the step composes its value and
gradient with the optimizer's update as ``make_pipeline_train_step`` does,
jitted with parameters and state donated.  Each chip holds its stage's
layers; embeddings, final norm and head are replicated.  The loop is closed:
each step is dispatched after the previous loss reached the host.

Awaiting a cell: no workload in ``BENCHMARK.json`` uses this path yet.  It
runs whole on four CPU devices (``tests/test_runs_cpu.py``) and its step
compiles for a described v5e:2x2, but it has never run on a chip.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import arith
from chipbench import session as S
from chipbench.families import gpt2 as fam
from chipbench.reference.gpt2 import readings, seed_key


class Session:
    chips = 4

    def __init__(self, conf: dict, traffic: dict, seed: int, devices):
        from repro.distributed.pipeline import (make_pipeline_train_fn,
                                                n_stages, pod_edge_ratios,
                                                stage_axes)
        from repro.optim import adamw
        from repro.optim.optimizers import OptState

        self.conf, self.traffic = conf, traffic
        pods, per_pod = traffic["mesh"]
        self.mesh = mesh = Mesh(
            np.array(devices[:pods * per_pod]).reshape(pods, per_pod),
            ("pod", "model"))
        self.ref_devices = list(mesh.devices.flat)     # stage order
        n_micro, mb, seq = (traffic["n_micro"], traffic["micro_batch"],
                            traffic["seq"])
        self.n_micro = n_micro
        self.tokens_per_step = n_micro * mb * seq
        cfg = fam.program_cfg(conf)
        ns = n_stages(mesh)
        n = mb * seq * conf["n_embd"]
        block = traffic["codec_block"]
        found = [{"after": f"block_{(s + 1) * cfg.n_layers // ns - 1}",
                  "k_per_block": arith.k_per_block(n, r, block)}
                 for s, r in enumerate(pod_edge_ratios(
                     mesh, traffic["base_ratio"])[:ns - 1]) if r > 1.0]
        self.plan_faults = S.check_edges(found, traffic["compressed_edges"])
        self.codec_edges = S.codec_edges(
            traffic, {e["after"]: n for e in traffic["compressed_edges"]},
            calls=n_micro)
        o = traffic["optimizer"]
        self.b1 = o["b1"]
        self.opt = opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                               weight_decay=o["weight_decay"])
        rep = NamedSharding(mesh, P())
        self.rep = rep
        with jax.set_mesh(mesh):
            loss_fn = make_pipeline_train_fn(cfg, mesh, n_micro,
                                             traffic["base_ratio"],
                                             use_kernel=traffic["use_kernel"])
            shapes = jax.eval_shape(lambda k: fam.stacked_init(conf, k),
                                    seed_key(0))
            stage = NamedSharding(mesh, P(stage_axes(mesh)))
            psh = {k: jax.tree_util.tree_map(
                lambda _, k=k: stage if k == "blocks" else rep, v)
                for k, v in shapes.items()}
            self._psh = psh
            self._make = jax.jit(lambda k: fam.stacked_init(conf, k),
                                 out_shardings=psh)
            self._init_state = jax.jit(
                opt.init, out_shardings=OptState(step=rep, inner={
                    "m": psh, "v": psh}))
            self._grad1 = jax.jit(lambda m: fam.stacked_reads(
                conf, jax.tree_util.tree_map(lambda x: x / (1 - self.b1), m)))
            self._delta = jax.jit(lambda p, k: fam.stacked_reads(
                conf, jax.tree_util.tree_map(
                    lambda a, b: a - b, p, jax.lax.with_sharding_constraint(
                        fam.stacked_init(conf, k), psh))))

            def step(params, state, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                params, state = opt.update(grads, state, params)
                return params, state, loss

            t1 = time.perf_counter()
            self.reset(seed)
            t0 = time.perf_counter()
            compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                self.params, self.state, self.feed.batches[0]).compile()
            self.compile_s = time.perf_counter() - t0
            self.phases = {"weights_and_batches": t0 - t1,
                           "compile": self.compile_s}
        self.hbm_bytes = S.bytes_needed(compiled)
        self._step = S.Dispatch(compiled)

    def _place(self, b):
        return {k: jax.device_put(v.reshape((self.n_micro, -1) + v.shape[1:]),
                                  self.rep) for k, v in b.items()}

    def reset(self, seed: int) -> None:
        self.seed = seed
        self.params = self.state = None
        gc.collect()
        with jax.set_mesh(self.mesh):
            self.params = self._make(seed_key(seed))
            self.state = self._init_state(self.params)
        self.feed, self.host_batches = S.make_feed(self.traffic, self.conf,
                                                   seed, self._place)

    def step(self) -> float:
        batch = self.feed.batch(0, 0)
        self.params, self.state, loss = self._step(self.params, self.state,
                                                   batch)
        return float(loss)

    def first_steps(self, steps: int):
        losses = [self.step()]
        with jax.set_mesh(self.mesh):
            grad1 = self._grad1(self.state.inner["m"])
        losses += [self.step() for _ in range(steps - 1)]
        with jax.set_mesh(self.mesh):
            delta = self._delta(self.params, seed_key(self.seed))
        return readings(losses, grad1, delta)

    def sync(self) -> None:
        jax.block_until_ready((self.params, self.state))

    def free(self) -> None:
        self.params = self.state = None
        gc.collect()
