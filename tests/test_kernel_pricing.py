"""The compression-pricing loop: KernelTiming telemetry -> fit_kernel_costs
-> EdgeCostModel.compress_seconds -> planner profitability guard / checker
invariant / simulated codec stream / controller calibration.

The §6 premise under test: compression must outrun the bandwidth it buys
back.  A plan whose fused-encode seconds exceed the wire seconds saved is
rejected at every layer — plan_adatopk skips the edge, ``repro.check``
flags a surviving one, and the simulator prices the codec span so the
throughput numbers say the same thing."""
import numpy as np
import pytest

from repro.core import (EdgeCostModel, network, plan_adatopk,
                        schedule_opfence, simulate_iteration)
from repro.core.compression import CompressionPlan
from repro.core.costmodel import KernelCostModel, fit_kernel_costs
from repro.core.executor import KernelTiming, TelemetrySink
from repro.check.costs import (check_compression_plan, check_cost_model,
                               verify_plan)
from repro.check.errors import CompressionCheckError
from repro.check.lint import lint_source
from repro.elastic import (ChurnTrace, ElasticController, TelemetryLog)
from helpers import mlp_chain


def _setup(n_layers=12, d=64, batch=8):
    g, shapes, params, inputs = mlp_chain(n_layers=n_layers, d=d, batch=batch)
    prof = g.annotate(shapes)
    cluster = network.paper_testbed(1, seed=0)
    sch = schedule_opfence(g, prof, cluster)
    return g, prof, cluster, sch


def _all_devices(cluster):
    return range(len(cluster.devices))


# ------------------------------------------------------------ fitting ----
def test_fit_kernel_costs_recovers_throughput():
    bps = 2.0e9
    window = {0: [(b, b / bps) for b in (1e6, 4e6, 16e6)]}
    fit = fit_kernel_costs(window)
    assert fit[0].bytes_per_second == pytest.approx(bps, rel=1e-12)
    assert fit[0].alpha == 0.0
    # degenerate devices are skipped, never priced as garbage
    assert fit_kernel_costs({1: [(0.0, 1.0)]}) == {}
    assert fit_kernel_costs({2: [(1e6, 0.0)]}) == {}


def test_kernel_cost_model_seconds():
    kc = KernelCostModel(alpha=1e-4, bytes_per_second=1e9)
    assert kc.seconds(1e9) == pytest.approx(1.0 + 1e-4)
    free = KernelCostModel()      # legacy default: compression is free
    assert free.seconds(1e12) == 0.0


# ---------------------------------------------------------- telemetry ----
def test_telemetry_log_windows_kernel_samples():
    log = TelemetryLog(window=5, mad_k=3.5)
    bps = 1.0e9
    for step in range(4):
        # two invocations per step fold into one per-step entry
        log.record_kernel_step(
            [KernelTiming(node=0, nbytes=1e6, seconds=1e6 / bps),
             KernelTiming(node=0, nbytes=3e6, seconds=3e6 / bps)],
            step=step)
    win = log.kernel_samples(min_steps=3)
    assert set(win) == {0}
    fit = fit_kernel_costs(win)
    assert fit[0].bytes_per_second == pytest.approx(bps, rel=1e-9)
    # below min_steps the device is withheld entirely
    log2 = TelemetryLog(window=5)
    log2.record_kernel_step([KernelTiming(node=1, nbytes=1e6,
                                          seconds=1e-3)], step=0)
    assert log2.kernel_samples(min_steps=3) == {}
    log2.clear()
    assert log2.n_kernel_samples == 0


def test_kernel_window_mad_rejects_spike():
    log = TelemetryLog(window=8, mad_k=3.5)
    bps = 1.0e9
    for step in range(7):
        log.record_kernel_step([KernelTiming(node=0, nbytes=1e6,
                                             seconds=1e6 / bps)], step=step)
    # one 100x-pace GC hiccup must not tilt the fit
    log.record_kernel_step([KernelTiming(node=0, nbytes=1e6,
                                         seconds=100e6 / bps)], step=7)
    fit = fit_kernel_costs(log.kernel_samples(min_steps=3))
    assert fit[0].bytes_per_second == pytest.approx(bps, rel=1e-6)


# ----------------------------------------------------------- pricing ----
def test_compress_seconds_zero_without_plan_or_costs():
    g, prof, cluster, sch = _setup()
    placement = sch.placement
    plan = plan_adatopk(g, prof, cluster, placement, 100.0)
    kcs = {d: KernelCostModel(bytes_per_second=1e9)
           for d in _all_devices(cluster)}
    dense_m = EdgeCostModel(g, prof, cluster, kernel_costs=kcs)
    no_kc_m = EdgeCostModel(g, prof, cluster, plan)
    priced = EdgeCostModel(g, prof, cluster, plan, kernel_costs=kcs)
    hits = 0
    for (a, n) in priced.cross_edges(placement):
        src = placement[a]
        assert dense_m.compress_seconds(a, n, src) == 0.0   # dense edge
        assert no_kc_m.compress_seconds(a, n, src) == 0.0   # legacy free
        got = priced.compress_seconds(a, n, src)
        if priced.ratio(a, n) > 1.0:
            hits += 1
            assert got == pytest.approx(
                kcs[src].seconds(priced.dense_bytes(a)), rel=1e-12)
    assert hits > 0


def test_stage_pace_includes_codec_stream():
    g, prof, cluster, sch = _setup()
    plan = plan_adatopk(g, prof, cluster, sch.placement, 100.0)
    base = EdgeCostModel(g, prof, cluster, plan)
    pace0 = base.stage_pace(sch)
    # a pathologically slow codec must dominate Eq. 3's max(C, R, E)
    slow = base.with_kernel_costs(
        {d: KernelCostModel(bytes_per_second=1.0)
         for d in _all_devices(cluster)})
    assert slow.stage_pace(sch) > 10.0 * pace0


# ----------------------------------------------- planner profitability ----
def test_plan_adatopk_drops_unprofitable_edges():
    g, prof, cluster, sch = _setup()
    placement = sch.placement
    free = plan_adatopk(g, prof, cluster, placement, 100.0)
    assert free.edge_ratio, "baseline plan compresses nothing"
    # codec slower than the wire: every edge fails §6's premise
    slow_m = EdgeCostModel(g, prof, cluster, kernel_costs={
        d: KernelCostModel(bytes_per_second=1.0)
        for d in _all_devices(cluster)})
    guarded = plan_adatopk(g, prof, cluster, placement, 100.0,
                           cost_model=slow_m)
    assert guarded.edge_ratio == {}
    # fast codec: the guard never fires, plan identical to the free one
    fast_m = EdgeCostModel(g, prof, cluster, kernel_costs={
        d: KernelCostModel(bytes_per_second=1e15)
        for d in _all_devices(cluster)})
    assert plan_adatopk(g, prof, cluster, placement, 100.0,
                        cost_model=fast_m).edge_ratio == free.edge_ratio


# ------------------------------------------------------- check gates ----
def test_check_rejects_unprofitable_plan():
    """Regression pin (ISSUE 8 acceptance): a plan whose encode cost
    exceeds the wire seconds saved must be rejected by repro.check."""
    g, prof, cluster, sch = _setup()
    placement = sch.placement
    plan = plan_adatopk(g, prof, cluster, placement, 100.0)
    assert plan.edge_ratio
    slow_m = EdgeCostModel(g, prof, cluster, kernel_costs={
        d: KernelCostModel(bytes_per_second=1.0)
        for d in _all_devices(cluster)})
    findings = check_compression_plan(g, prof, plan, placement,
                                      cost_model=slow_m)
    codes = {f.code for f in findings}
    assert "compression-unprofitable" in codes
    with pytest.raises(CompressionCheckError):
        verify_plan(g, prof, plan, placement=placement, cost_model=slow_m)
    # the installed-model view flags the same edges
    model_findings = check_cost_model(slow_m.with_plan(plan), placement)
    assert "compression-unprofitable" in {f.code for f in model_findings}
    # a profitable codec passes every gate
    fast_m = slow_m.with_kernel_costs(
        {d: KernelCostModel(bytes_per_second=1e15)
         for d in _all_devices(cluster)})
    assert verify_plan(g, prof, plan, placement=placement,
                       cost_model=fast_m) == []
    assert not [f for f in check_cost_model(fast_m.with_plan(plan),
                                            placement)
                if f.code == "compression-unprofitable"]


def test_check_flags_garbage_kernel_cost():
    g, prof, cluster, sch = _setup()
    bad = EdgeCostModel(g, prof, cluster, kernel_costs={
        0: KernelCostModel(alpha=float("nan"), bytes_per_second=1e9)})
    assert "bad-kernel-cost" in {
        f.code for f in check_cost_model(bad, sch.placement)}


# --------------------------------------------------------- simulation ----
def test_sim_codec_stream_emits_samples_and_busy():
    g, prof, cluster, sch = _setup()
    placement = sch.placement
    plan = plan_adatopk(g, prof, cluster, placement, 100.0)
    kcs = {d: KernelCostModel(bytes_per_second=5e8)
           for d in _all_devices(cluster)}
    model = EdgeCostModel(g, prof, cluster, plan, kernel_costs=kcs)
    sink = TelemetrySink()
    n_micro = 2
    res = simulate_iteration(g, prof, sch, cluster, plan, n_micro=n_micro,
                             telemetry=sink, cost_model=model)
    assert res.compress_busy > 0.0
    assert sink.kernel_samples
    # each sample prices exactly the model's compress_seconds for its edge
    per_dev = {}
    for s in sink.kernel_samples:
        assert s.seconds == pytest.approx(
            kcs[s.node].seconds(s.nbytes), rel=1e-12)
        per_dev[s.node] = per_dev.get(s.node, 0.0) + s.seconds
    assert res.compress_busy == pytest.approx(sum(per_dev.values()),
                                              rel=1e-12)
    # FP + BP, n_micro each, per compressed cross edge
    n_compressed = sum(1 for e in model.cross_edges(placement)
                       if model.ratio(*e) > 1.0)
    assert len(sink.kernel_samples) == 2 * n_micro * n_compressed
    # legacy model (no kernel costs): codec is free, no samples
    res0 = simulate_iteration(g, prof, sch, cluster, plan, n_micro=n_micro,
                              telemetry=TelemetrySink())
    assert res0.compress_busy == 0.0
    # the codec span sits on the step's critical path only via overlap:
    # a priced step is never faster, and never slower than fully serial
    assert res0.iteration_time <= res.iteration_time \
        <= res0.iteration_time + res.compress_busy + 1e-12


def test_sim_codec_span_double_buffers():
    """A moderately slow codec hides behind next-micro-batch compute (the
    overlap discount): iteration time grows by less than the full codec
    busy seconds."""
    g, prof, cluster, sch = _setup(n_layers=12, d=256)
    placement = sch.placement
    plan = plan_adatopk(g, prof, cluster, placement, 100.0)
    base = simulate_iteration(g, prof, sch, cluster, plan, n_micro=4)
    kcs = {d: KernelCostModel(bytes_per_second=2e10)
           for d in _all_devices(cluster)}
    model = EdgeCostModel(g, prof, cluster, plan, kernel_costs=kcs)
    res = simulate_iteration(g, prof, sch, cluster, plan, n_micro=4,
                             cost_model=model)
    assert res.compress_busy > 0.0
    delta = res.iteration_time - base.iteration_time
    assert delta < res.compress_busy      # some codec time was overlapped


# --------------------------------------------------- controller loop ----
def test_controller_calibrates_kernel_cost_belief():
    """Ground-truth kernel costs in the sim surface as KernelTiming
    telemetry; the controller's calibration fits them back into
    kernel_cost_belief and plans against the belief."""
    g, prof, cluster, sch = _setup()
    bps = 1.0e9
    kcs = {d: KernelCostModel(bytes_per_second=bps)
           for d in _all_devices(cluster)}
    ctrl = ElasticController(g, prof, cluster, ChurnTrace(()), n_micro=2,
                             planner="joint", joint_ratio=64.0,
                             calibrate_interval=3, calibrate_min_samples=3,
                             kernel_costs=kcs)
    assert ctrl.kernel_cost_belief == {}
    ctrl.run(steps=8)
    assert ctrl.kernel_cost_belief, "no kernel cost fitted"
    for dev, kc in ctrl.kernel_cost_belief.items():
        assert kc.bytes_per_second == pytest.approx(bps, rel=1e-6), dev
    believed = ctrl.believed_model()
    assert believed.kernel_costs == ctrl.kernel_cost_belief


# ---------------------------------------------------------------- lint ----
def test_lint_flags_kernel_dispatch_bypass():
    src = "def f(x, k):\n    return topk_mask(x, k)\n"
    hits = [f for f in lint_source(src, "core/rad.py")
            if f.code == "kernel-dispatch-bypass"]
    assert len(hits) == 1 and hits[0].where == "core/rad.py:2"
    # threading the policy through satisfies the rule
    ok = "def f(x, k, uk):\n    return topk_mask(x, k, use_kernel=uk)\n"
    assert not [f for f in lint_source(ok, "distributed/pipeline.py")
                if f.code == "kernel-dispatch-bypass"]
    # outside the hot-path scopes the rule does not apply
    assert not [f for f in lint_source(src, "core/compression.py")
                if f.code == "kernel-dispatch-bypass"]


# ---------------------------------------------------------------- bench ----
def test_kernel_bench_smoke():
    import benchmarks.kernel_bench as bench
    rows = []
    result = bench.run(lambda *a: rows.append(a))["kernel"]
    assert result["parity"] == 1.0
    assert result["speedup"] > 0
    structure = dict(kv.split("=") for kv in rows[-1][2].split(","))
    assert structure["value_slots"] == "41"        # exactly k per block
    assert int(structure["vmem_bytes"]) == result["vmem_bytes"]
