"""RAD training on one chip through the launcher.

``repro.launch.train.fusion_job`` plans the job (OP-Fence on the testbed,
the traffic's compression plan) and builds the jitted step; the benchmark
gives it weights made from the seed, compiles the step once and hands the
compiled step back to the job.  ``train_fusion`` then drives every step,
those of set-up and those of the window alike: one step at a time, each
dispatched after the previous loss reached the host.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import numpy as np

from chipbench import arith
from chipbench import session as S
from chipbench.families import gpt2 as fam
from chipbench.reference.gpt2 import (init_leaf, init_params, leaf_names,
                                      read_leaves, readings, seed_key)


class Session:
    chips = 1

    def __init__(self, conf: dict, traffic: dict, seed: int, devices):
        from repro.launch.train import fusion_job
        from repro.optim import adamw

        self.conf, self.traffic = conf, traffic
        self.device = devices[0]
        self.ref_devices = [self.device]
        o = traffic["optimizer"]
        self.b1 = o["b1"]
        self.opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        batch, seq = traffic["batch"], traffic["seq"]
        self.tokens_per_step = batch * seq
        t0 = time.perf_counter()
        job = fusion_job(fam.program_cfg(conf), self.opt, batch=batch,
                         seq=seq, compress=traffic["compress"],
                         ratio=traffic["base_ratio"],
                         testbed=traffic["testbed"])
        block = traffic["codec_block"]
        sizes, found = {}, []
        for (producer, _), ratio in job.plan.as_mapping().items():
            n = int(np.prod(job.prof[producer].out_shape))
            sizes[producer] = n
            if ratio > 1.0:
                found.append({"after": producer, "k_per_block":
                              arith.k_per_block(n, ratio, block)})
        self.plan_faults = S.check_edges(found, traffic["compressed_edges"])
        self.codec_edges = S.codec_edges(traffic, sizes, calls=1)
        # the job's own weights come from a fixed key: drop them first
        job.params = job.opt_state = None
        gc.collect()
        names = leaf_names(conf)
        self._make = jax.jit(lambda k: fam.rad_params(
            conf, init_params(conf, k, names)))
        self._grad1 = jax.jit(lambda m: read_leaves({
            n: a / (1 - self.b1) for n, a in fam.rad_leaves(conf, m).items()}))
        self._delta = jax.jit(lambda p, k: read_leaves({
            n: a - init_leaf(conf, k, n)
            for n, a in fam.rad_leaves(conf, p).items()}))
        self.job = job
        t1 = time.perf_counter()
        self.reset(seed)
        t2 = time.perf_counter()
        compiled = job.step.lower(job.params, job.opt_state,
                                  self.feed.batches[0]).compile()
        self.compile_s = time.perf_counter() - t2
        #: host seconds of set-up's parts (printed by the run)
        self.phases = {"job": t1 - t0, "weights_and_batches": t2 - t1,
                       "compile": self.compile_s}
        self.hbm_bytes = S.bytes_needed(compiled)
        job.step = S.Dispatch(compiled)

    def reset(self, seed: int) -> None:
        """Weights, optimizer state and batches of ``seed``."""
        self.seed = seed
        self.job.params = self.job.opt_state = None
        gc.collect()
        self.job.params = self._make(seed_key(seed))
        self.job.opt_state = jax.jit(self.opt.init)(self.job.params)
        self.feed, self.host_batches = S.make_feed(
            self.traffic, self.conf, seed,
            functools.partial(jax.device_put, device=self.device))

    def step(self) -> float:
        from repro.launch.train import train_fusion
        return train_fusion(self.job, self.feed, 1)[0]

    def first_steps(self, steps: int):
        """The first ``steps`` steps through the window's own call, read
        as the comparison needs them."""
        losses = [self.step()]
        grad1 = self._grad1(self.job.opt_state.inner["m"])
        losses += [self.step() for _ in range(steps - 1)]
        delta = self._delta(self.job.params, seed_key(self.seed))
        return readings(losses, grad1, delta)

    def sync(self) -> None:
        jax.block_until_ready((self.job.params, self.job.opt_state))

    def free(self) -> None:
        self.job.params = self.job.opt_state = None
        gc.collect()
