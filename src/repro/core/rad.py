"""Remote automatic differentiation (FusionLLM §3.3).

No ML framework differentiates across machine boundaries; FusionLLM's answer
is stage-local autodiff plus boundary exchange: every CompNode runs FP/BP on
its own sub-DAG and only boundary activations (FP) and boundary gradients
(BP, keyed ``producer->user``) travel between CompNodes.

JAX mapping: each sub-DAG becomes a pure function
``f_k(params_k, ext_acts, inputs) -> (sends, loss_k)``; the forward sweep
chains them in stage order and *records* ``jax.vjp`` closures; the backward
sweep calls them in reverse, routing each cotangent back over the edge it
belongs to.  Compression (AdaTopK) is applied to the transported tensor on
both directions of every cross-node edge — outside any stage's autodiff,
exactly like the real transport (the consumer trains on the sparsified
activation; the producer backpropagates the sparsified gradient).

Each stage's forward and backward, and the codec on each compressed edge in
each direction, run inside a ``jax.named_scope`` (:mod:`repro.obs.scopes`),
so that a profiler trace of the jitted step attributes every device op to
its stage or edge.

``pipeline_train_step`` with no compression is bit-identical to single-device
``jax.grad`` over :meth:`OpGraph.apply` (tested), which is the correctness
contract of RAD.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .compression import (CompressionPlan, KernelPolicy, compress_for_edge,
                          dense_payload_bytes, plan_none)
from .opgraph import OpGraph, OpType, SubDag
from ..obs.scopes import edge_scope, stage_scope
from ..obs.trace import CAT_ENCODE


Params = Mapping[str, Any]

# Measured-wall-clock hook: (stage_index, backward, seconds) per stage call.
# The DecentralizedRuntime wraps this into StepTiming telemetry samples.
TimingCb = Callable[[int, bool, float], None]

# Measured-wall-clock codec hook: (stage_index, backward, seconds,
# dense_bytes) per compressed boundary edge.  The DecentralizedRuntime wraps
# this into KernelTiming telemetry samples — the raw material of
# fit_kernel_costs calibration.
KernelCb = Callable[[int, bool, float, float], None]


def _traced_compress(trace, name: str, track: str, backward: bool,
                     ratio: float, fn, kernel_cb: Optional[KernelCb] = None,
                     stage: int = 0, dense_bytes: float = 0.0, *,
                     scope: str):
    """Run one boundary compression inside the device scope ``scope``,
    recording a wall-clock encode span when tracing and a ``kernel_cb``
    timing sample when instrumented.  The decode half is fused into the
    same op (a kernel-dispatched topk_mask is encode→decode of the wire
    format), so both cover the whole codec; ``ratio<=1`` edges transport
    dense and record nothing."""
    if ratio <= 1.0:
        return fn()
    traced = trace is not None and getattr(trace, "enabled", False)
    with jax.named_scope(scope):
        if not traced and kernel_cb is None:
            return fn()
        t0 = time.perf_counter() if kernel_cb is not None else 0.0
        if traced:
            with trace.region(CAT_ENCODE, name, track,
                              args={"ratio": ratio, "backward": backward}):
                out = fn()
                jax.block_until_ready(out)
        else:
            out = fn()
            jax.block_until_ready(out)
    if kernel_cb is not None:
        kernel_cb(stage, backward, time.perf_counter() - t0, dense_bytes)
    return out


def make_stage_fn(graph: OpGraph, subdag: SubDag
                  ) -> Callable[[Params, Mapping[str, jax.Array], Mapping[str, jax.Array]],
                                Tuple[Dict[str, jax.Array], jax.Array]]:
    """Build the pure function executed by one CompNode.

    Args: ``params`` for this sub-DAG's parametric ops; ``ext_acts`` —
    activations received from other CompNodes (keys = producer op names,
    i.e. ``subdag.required_acti``); ``inputs`` — placeholder/variable values
    owned by this sub-DAG.  Returns (sends, loss) where ``sends`` maps each
    ``send_acti`` op name to its output and ``loss`` sums this sub-DAG's loss
    nodes (0.0 if none).
    """
    topo = [n for n in graph.topo_order() if n in subdag.node_set]

    def stage_fn(params: Params, ext_acts: Mapping[str, jax.Array],
                 inputs: Mapping[str, jax.Array]
                 ) -> Tuple[Dict[str, jax.Array], jax.Array]:
        vals: Dict[str, jax.Array] = dict(ext_acts)
        loss = jnp.asarray(0.0, dtype=jnp.float32)
        for n in topo:
            node = graph.nodes[n]
            if node.op_type in (OpType.PLACEHOLDER, OpType.VARIABLE):
                vals[n] = inputs[n]
                continue
            args = [vals[a] for a in node.args]
            out = node.apply_fn(params.get(n), *args) if node.apply_fn else args[0]
            vals[n] = out
            if node.op_type is OpType.LOSS:
                loss = loss + jnp.sum(out).astype(jnp.float32)
        sends = {n: vals[n] for n in subdag.send_acti}
        return sends, loss

    return stage_fn


@dataclasses.dataclass
class PipelineProgram:
    """Compiled stage plan: stage functions in pipeline order plus routing
    tables (which stage consumes which producer's output)."""

    graph: OpGraph
    subdags: List[SubDag]
    stage_fns: List[Callable]
    # consumer routing: producer op -> list of (consumer_stage_idx)
    consumers: Dict[str, List[int]]
    owner_stage: Dict[str, int]

    @staticmethod
    def build(graph: OpGraph, subdags: Sequence[SubDag]) -> "PipelineProgram":
        subdags = list(subdags)
        owner: Dict[str, int] = {}
        for si, sd in enumerate(subdags):
            for n in sd.node_names:
                owner[n] = si
        consumers: Dict[str, List[int]] = {}
        for si, sd in enumerate(subdags):
            for a in sd.required_acti:
                consumers.setdefault(a, []).append(si)
        return PipelineProgram(
            graph=graph, subdags=subdags,
            stage_fns=[make_stage_fn(graph, sd) for sd in subdags],
            consumers=consumers, owner_stage=owner)

    def split_params(self, params: Params) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = [{} for _ in self.subdags]
        for name, p in params.items():
            out[self.owner_stage[name]][name] = p
        return out

    def split_inputs(self, inputs: Mapping[str, jax.Array],
                     variables: Optional[Mapping[str, jax.Array]] = None
                     ) -> List[Dict[str, jax.Array]]:
        merged = dict(inputs)
        merged.update(variables or {})
        out: List[Dict[str, jax.Array]] = [{} for _ in self.subdags]
        for si, sd in enumerate(self.subdags):
            for n in sd.node_names:
                node = self.graph.nodes[n]
                if node.op_type in (OpType.PLACEHOLDER, OpType.VARIABLE):
                    out[si][n] = merged[n]
        return out


def pipeline_forward(prog: PipelineProgram, params: Params,
                     inputs: Mapping[str, jax.Array],
                     plan: Optional[CompressionPlan] = None,
                     use_kernel: KernelPolicy = False,
                     compress_bwd: bool = True,
                     timing_cb: Optional[TimingCb] = None,
                     trace: Optional[Any] = None,
                     kernel_cb: Optional[KernelCb] = None
                     ) -> Tuple[jax.Array, List[Any], List[Dict[str, jax.Array]]]:
    """Forward sweep.  Returns (total_loss, vjp closures per stage, the
    per-stage received ext_acts — needed to key backward cotangents).
    ``timing_cb(stage, backward=False, seconds)`` receives each stage's
    measured host wall-clock (telemetry hook; None = no instrumentation);
    ``trace`` additionally records wall-clock ``compress.encode`` spans per
    compressed boundary edge; ``kernel_cb(stage, backward, seconds,
    dense_bytes)`` receives each compressed edge's measured codec time."""
    plan = plan or plan_none(prog.graph, prog.owner_stage)
    stage_params = prog.split_params(params)
    stage_inputs = prog.split_inputs(inputs)
    mailbox: Dict[Tuple[str, int], jax.Array] = {}  # (producer, consumer_stage)
    vjps: List[Any] = []
    received: List[Dict[str, jax.Array]] = []
    total_loss = jnp.asarray(0.0, dtype=jnp.float32)

    for si, (fn, sd) in enumerate(zip(prog.stage_fns, prog.subdags)):
        ext = {a: mailbox[(a, si)] for a in sd.required_acti}
        received.append(ext)
        t0 = time.perf_counter() if timing_cb else 0.0
        with jax.named_scope(stage_scope(si, backward=False)):
            (sends, loss), vjp_fn = jax.vjp(
                lambda p, e: fn(p, e, stage_inputs[si]), stage_params[si],
                ext)
        if timing_cb:
            # async dispatch returns before the compute runs — force it so
            # the sample measures stage execution, not dispatch overhead
            jax.block_until_ready((sends, loss))
            timing_cb(si, False, time.perf_counter() - t0)
        vjps.append(vjp_fn)
        total_loss = total_loss + loss
        # transport: compress per edge (producer -> each consumer stage link)
        for a, out in sends.items():
            for cj in prog.consumers.get(a, []):
                consumer_ops = [n for n in prog.subdags[cj].node_names
                                if a in prog.graph.nodes[n].args]
                # one physical message per (producer, consumer CompNode); the
                # plan is keyed per (producer op, consumer op) — same ratio
                # for all consumers on one CompNode by construction.
                ratio = max([plan.ratio(a, c) for c in consumer_ops] or [1.0])
                mailbox[(a, cj)] = _traced_compress(
                    trace, f"enc {a}->s{cj}", f"stage{si}", False, ratio,
                    lambda out=out, ratio=ratio: compress_for_edge(
                        out, ratio, use_kernel, compress_bwd),
                    kernel_cb=kernel_cb, stage=si,
                    dense_bytes=dense_payload_bytes(out),
                    scope=edge_scope(a, cj, backward=False))
    return total_loss, vjps, received


def pipeline_backward(prog: PipelineProgram, vjps: List[Any],
                      received: List[Dict[str, jax.Array]],
                      plan: Optional[CompressionPlan] = None,
                      use_kernel: KernelPolicy = False,
                      timing_cb: Optional[TimingCb] = None,
                      trace: Optional[Any] = None,
                      kernel_cb: Optional[KernelCb] = None) -> Dict[str, Any]:
    """Backward sweep in reverse stage order; boundary gradients are
    compressed on the same links as their forward activations."""
    plan = plan or plan_none(prog.graph, prog.owner_stage)
    n_stages = len(prog.subdags)
    # cotangents awaiting each stage's sends: (producer, producer_stage) -> g
    grad_mail: Dict[str, jax.Array] = {}
    grads: Dict[str, Any] = {}

    for si in range(n_stages - 1, -1, -1):
        sd = prog.subdags[si]
        sends_cot = {}
        for a in sd.send_acti:
            g = grad_mail.get(a)
            if g is None:
                # consumer never contributed (e.g. consumer had no grad path)
                shape_src = received_shape = None
                raise RuntimeError(f"missing boundary gradient for {a!r}")
            sends_cot[a] = g
        loss_cot = jnp.asarray(1.0, dtype=jnp.float32)
        t0 = time.perf_counter() if timing_cb else 0.0
        with jax.named_scope(stage_scope(si, backward=True)):
            p_cot, ext_cot = vjps[si]((sends_cot, loss_cot))
        if timing_cb:
            jax.block_until_ready((p_cot, ext_cot))
            timing_cb(si, True, time.perf_counter() - t0)
        grads.update(p_cot)
        # route ext cotangents back to producers, compressed per link
        for a, g in ext_cot.items():
            producer_ops_here = [n for n in sd.node_names
                                 if a in prog.graph.nodes[n].args]
            ratio = max([plan.ratio(a, c) for c in producer_ops_here] or [1.0])
            g = _traced_compress(
                trace, f"enc grad({a})", f"stage{si}", True, ratio,
                lambda g=g, ratio=ratio: compress_for_edge(g, ratio,
                                                           use_kernel),
                kernel_cb=kernel_cb, stage=si,
                dense_bytes=dense_payload_bytes(g),
                scope=edge_scope(a, si, backward=True))
            grad_mail[a] = grad_mail[a] + g if a in grad_mail else g
    return grads


def pipeline_loss_and_grad(prog: PipelineProgram, params: Params,
                           inputs: Mapping[str, jax.Array],
                           plan: Optional[CompressionPlan] = None,
                           use_kernel: KernelPolicy = False,
                           timing_cb: Optional[TimingCb] = None,
                           trace: Optional[Any] = None,
                           kernel_cb: Optional[KernelCb] = None
                           ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One RAD iteration (all stages, one micro-batch)."""
    loss, vjps, received = pipeline_forward(prog, params, inputs, plan,
                                            use_kernel, timing_cb=timing_cb,
                                            trace=trace, kernel_cb=kernel_cb)
    grads = pipeline_backward(prog, vjps, received, plan, use_kernel,
                              timing_cb=timing_cb, trace=trace,
                              kernel_cb=kernel_cb)
    return loss, grads


def pipeline_train_step(prog: PipelineProgram, params: Params,
                        micro_batches: Sequence[Mapping[str, jax.Array]],
                        plan: Optional[CompressionPlan] = None,
                        use_kernel: KernelPolicy = False
                        ) -> Tuple[jax.Array, Dict[str, Any]]:
    """GPipe-style accumulation over micro-batches (paper Eq. 3 schedule;
    numerically the order does not matter, the executor models the timing)."""
    total_loss = jnp.asarray(0.0, dtype=jnp.float32)
    acc: Optional[Dict[str, Any]] = None
    for mb in micro_batches:
        loss, grads = pipeline_loss_and_grad(prog, params, mb, plan, use_kernel)
        total_loss = total_loss + loss
        if acc is None:
            acc = grads
        else:
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
    n = float(len(micro_batches))
    acc = jax.tree_util.tree_map(lambda g: g / n, acc)
    return total_loss / n, acc


def init_ef_state(prog: PipelineProgram, params: Params,
                  inputs: Mapping[str, jax.Array]) -> Dict[str, jax.Array]:
    """Zero error-feedback residuals, one per backward (gradient) edge —
    keyed by producer op.  Shapes come from a throwaway forward."""
    _, _, received = pipeline_forward(prog, params, inputs)
    shapes: Dict[str, jax.Array] = {}
    for ext in received:
        for a, v in ext.items():
            shapes[a] = jnp.zeros_like(v)
    return shapes


def pipeline_loss_and_grad_ef(prog: PipelineProgram, params: Params,
                              inputs: Mapping[str, jax.Array],
                              plan: CompressionPlan,
                              ef_state: Dict[str, jax.Array],
                              use_kernel: KernelPolicy = False,
                              timing_cb: Optional[TimingCb] = None,
                              trace: Optional[Any] = None,
                              kernel_cb: Optional[KernelCb] = None
                              ) -> Tuple[jax.Array, Dict[str, Any],
                                         Dict[str, jax.Array]]:
    """RAD iteration with error feedback on the BACKWARD (gradient) edges
    (beyond-paper: EF-SGD residual memory; motivated by the measured
    divergence of plain compressed training, EXPERIMENTS.md §Convergence).

    Forward activations compress exactly as the paper's transport; the
    gradient of each cross-node edge sends TopK(g + residual) and keeps
    what was dropped for the next step."""
    from .compression import ratio_to_k, topk_mask

    # forward-only transport compression here; the gradient direction is
    # compressed below, WITH the residual memory (otherwise the custom_vjp
    # would sparsify the cotangent before EF sees it — double compression).
    loss, vjps, received = pipeline_forward(prog, params, inputs, plan,
                                            use_kernel, compress_bwd=False,
                                            timing_cb=timing_cb, trace=trace,
                                            kernel_cb=kernel_cb)
    n_stages = len(prog.subdags)
    grad_mail: Dict[str, jax.Array] = {}
    grads: Dict[str, Any] = {}
    new_ef = dict(ef_state)

    for si in range(n_stages - 1, -1, -1):
        sd = prog.subdags[si]
        sends_cot = {a: grad_mail[a] for a in sd.send_acti}
        t0 = time.perf_counter() if timing_cb else 0.0
        with jax.named_scope(stage_scope(si, backward=True)):
            p_cot, ext_cot = vjps[si]((sends_cot,
                                       jnp.asarray(1.0, jnp.float32)))
        if timing_cb:
            jax.block_until_ready((p_cot, ext_cot))
            timing_cb(si, True, time.perf_counter() - t0)
        grads.update(p_cot)
        for a, g in ext_cot.items():
            consumer_ops = [n for n in sd.node_names
                            if a in prog.graph.nodes[n].args]
            ratio = max([plan.ratio(a, c) for c in consumer_ops] or [1.0])
            if ratio > 1.0:
                corrected = g + ef_state[a].astype(g.dtype)
                k = ratio_to_k(int(np.prod(g.shape)), ratio)
                sent = _traced_compress(
                    trace, f"enc ef({a})", f"stage{si}", True, ratio,
                    lambda corrected=corrected, k=k: topk_mask(
                        corrected, k, use_kernel=use_kernel),
                    kernel_cb=kernel_cb, stage=si,
                    dense_bytes=dense_payload_bytes(g),
                    scope=edge_scope(a, si, backward=True))
                new_ef[a] = corrected - sent
                g = sent
            grad_mail[a] = grad_mail[a] + g if a in grad_mail else g
    return loss, grads, new_ef


def single_device_loss_and_grad(graph: OpGraph, params: Params,
                                inputs: Mapping[str, jax.Array]
                                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Reference semantics: whole graph on one device, plain ``jax.grad`` —
    the ground truth RAD must reproduce when compression is off."""

    def loss_fn(p):
        vals = graph.apply(p, inputs)
        return sum(jnp.sum(vals[ln]).astype(jnp.float32)
                   for ln in graph.loss_nodes())

    return jax.value_and_grad(loss_fn)(dict(params))
