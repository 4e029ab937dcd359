"""Device scopes of the RAD step and the training loop's host spans.

The smoke-width gpt2-xl RAD step, built by ``fusion_job`` and compiled on
the CPU, labels every instruction that does work with its stage, boundary
edge or the optimizer (``repro.obs.scopes``), as the benchmark's reader of
a compiled module (``chipbench/scope_reduce.py``) sees them;
``train_fusion`` writes ``train.*`` spans into a running profiler's host
plane, and so does an enabled ``TraceRecorder.region``."""
import math
import re
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import arith
from chipbench import scope_reduce as R
from repro.configs import resolve
from repro.data import SyntheticLM
from repro.launch import cache
from repro.launch.train import device_batch, fusion_job, train_fusion
from repro.obs import TraceRecorder
from repro.obs import scopes as S
from repro.obs.trace import CAT_ENCODE

BATCH, SEQ = 2, 32
#: instructions that do no work of their own
NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


def _scopes(hlo):
    return R.instruction_scopes(hlo, S.classify)


def _job(compress):
    from repro.optim import adamw
    cfg = resolve("gpt2-xl").smoke
    return fusion_job(cfg, adamw(1e-3), batch=BATCH, seq=SEQ,
                      compress=compress)


def _data(cfg):
    return SyntheticLM(vocab=cfg.vocab, seq_len=SEQ, seed=0, order=1)


def _compiled_text(job):
    cfg = resolve("gpt2-xl").smoke
    batch = device_batch(_data(cfg), BATCH, 0)
    return job.step.lower(job.params, job.opt_state, batch).compile().as_text()


@pytest.fixture(scope="module")
def adatopk():
    job = _job("adatopk")
    return job, _compiled_text(job)


def _entry(hlo):
    """(name, opcode, text) of each instruction of the entry computation."""
    lines = hlo.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    out = []
    for line in lines[start + 1:]:
        if line.strip() == "}":
            break
        name, text = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$",
                              line).groups()
        op = re.search(r"\s([a-z][\w\-]*)\(", " " + text).group(1)
        out.append((name, op, text))
    return out


def _planned_producers(job):
    return {p for (p, _), r in job.plan.as_mapping().items() if r > 1.0}


def test_every_instruction_doing_work_has_a_scope(adatopk):
    job, hlo = adatopk
    table = _scopes(hlo)
    entry = _entry(hlo)
    params = {n for n, op, _ in entry if op == "parameter"}
    left = [(n, op, t) for n, op, t in entry
            if op not in NO_WORK and table[n] is None]
    # what stays unscoped: copies of the step's parameters whose users lie
    # in several scopes (a weight read by its stage's forward, backward and
    # the optimizer)
    for name, op, text in left:
        operand = re.search(r"copy\(%?([\w.\-]+)\)", text)
        assert op == "copy" and operand and operand.group(1) in params, name
    assert len(left) < 0.05 * sum(op not in NO_WORK for _, op, _ in entry)


def test_stages_edges_and_optimizer_all_appear(adatopk):
    job, hlo = adatopk
    found = {s for s in _scopes(hlo).values() if s is not None}
    stages = {(s.where, s.direction) for s in found if s.kind == S.STAGE}
    assert stages == {(f"s{i}", d) for i in range(job.n_stages)
                      for d in S.DIRECTIONS}
    edges = {(s.where.split("/")[0], s.direction) for s in found
             if s.kind == S.CODEC}
    planned = _planned_producers(job)
    assert planned and edges == {(p, d) for p in planned
                                 for d in S.DIRECTIONS}
    assert S.Scope(S.OPTIM, "", "") in found


def test_rad_step_opens_no_pipe_scope(adatopk):
    """The GPipe step's scopes never appear in the RAD step's program."""
    _, hlo = adatopk
    assert not re.search(r'op_name="[^"]*\bpipe/', hlo)
    assert {s.step for s in _scopes(hlo).values() if s is not None} == {
        S.RAD}


def test_no_edge_scope_without_compression():
    job = _job("none")
    assert not _planned_producers(job)
    hlo = _compiled_text(job)
    assert "rad/edge" not in hlo
    kinds = {s.kind for s in _scopes(hlo).values() if s}
    assert kinds == {S.STAGE, S.OPTIM}


@pytest.mark.parametrize("op_name, label", [
    ("jit(step)/rad/s3/fwd/jvp()/dot_general", "rad/s3/fwd"),
    ("jit(step)/rad/s3/bwd/transpose(jvp())/mul", "rad/s3/bwd"),
    ("jit(step)/rad/edge/head/s14/fwd/jit(_encode_pallas)/pallas_call",
     "rad/edge/head/s14/fwd"),
    ("jit(step)/rad/s2/bwd/rad/edge/block_6/s7/bwd/select_n",
     "rad/edge/block_6/s7/bwd"),
    ("jit(step)/optim/sub", "optim"),
    ("jit(step)/add", None),
    ("jit(step)/rad/s12x/fwd/add", None),
    ("jit(step)/optimizer/add", None),
])
def test_classify(op_name, label):
    got = S.classify(op_name)
    assert (None if got is None else str(got)) == label


def test_scope_names_round_trip_through_classify():
    assert str(S.classify("jit(f)/" + S.stage_scope(4, backward=True)
                          + "/add")) == "rad/s4/bwd"
    edge = S.edge_scope("block_6", 7, backward=False)
    assert S.classify(f"jit(f)/{edge}/x") == S.Scope(S.CODEC, "block_6/s7",
                                                     "fwd")
    with pytest.raises(ValueError):
        S.edge_scope("a/b", 1, backward=False)


HLO = """HloModule m, entry_computation_layout={(f32[4,4]{1,0})->f32[4,4]{1,0}}

%fused_computation.2 (q: f32[4,4]) -> (f32[4,4], f32[4,4]) {
  %q = f32[4,4]{1,0} parameter(0)
  %g = f32[4,4]{1,0} dot(%q, %q), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/rad/s2/bwd/transpose(jvp())/dot_general"}
  %m = f32[4,4]{1,0} multiply(%g, %g), metadata={op_name="jit(f)/optim/mul"}
  %u = f32[4,4]{1,0} add(%m, %q), metadata={op_name="jit(f)/optim/add"}
  ROOT %t = (f32[4,4]{1,0}, f32[4,4]{1,0}) tuple(%g, %u)
}

%fused_computation.3 (r: f32[4,4]) -> (f32[4,4], f32[4,4]) {
  %r = f32[4,4]{1,0} parameter(0)
  %h = f32[4,4]{1,0} negate(%r), metadata={op_name="jit(f)/rad/s2/bwd/neg"}
  %v = f32[4,4]{1,0} multiply(%h, %h), metadata={op_name="jit(f)/optim/mul"}
  %w = f32[4,4]{1,0} add(%v, %r), metadata={op_name="jit(f)/optim/add"}
  ROOT %t.3 = (f32[4,4]{1,0}, f32[4,4]{1,0}) tuple(%h, %w)
}

ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4]{1,0} parameter(0)
  %copy.1 = f32[4,4]{1,0} copy(%x)
  %fusion.2 = (f32[4,4]{1,0}, f32[4,4]{1,0}) fusion(%copy.1), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(f)/optim/add"}
  %fusion.3 = (f32[4,4]{1,0}, f32[4,4]{1,0}) fusion(%x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/rad/s2/bwd/neg"}
  %copy.2 = f32[4,4]{1,0} copy(%x)
  %add.1 = f32[4,4]{1,0} add(%copy.2, %x), metadata={op_name="jit(f)/optim/add"}
  %copy.3 = f32[4,4]{1,0} copy(%add.1)
  ROOT %mul.1 = f32[4,4]{1,0} multiply(%copy.3, %copy.3), metadata={op_name="jit(f)/mul"}
}
"""


def test_scopes_of_instructions_whose_op_name_misleads_or_is_missing():
    table = {k: (str(v) if v else None)
             for k, v in _scopes(HLO).items()}
    # fusion bodies are left out
    assert not {"g", "m", "h", "v"} & set(table)
    # a gradient's matmul with the optimizer's update fused after it: the
    # matmul decides, whatever op_name XLA gave the fusion and however many
    # of the optimizer's ops it holds
    assert table["fusion.2"] == "rad/s2/bwd"
    # without a matmul, the fusion's own op_name decides
    assert table["fusion.3"] == "rad/s2/bwd"
    # without an op_name: its one user's scope, else its operands' one
    assert table["copy.1"] == "rad/s2/bwd"
    assert table["copy.2"] == "optim"
    assert table["copy.3"] == "optim"
    assert table["mul.1"] is None and table["x"] is None


_SHAPE = re.compile(r"^\(?[a-z]\w*\[([\d,]*)\]")


def _dims(text):
    """Dimensions of an instruction's (first) result, from its text."""
    return [int(d) for d in _SHAPE.match(text).group(1).split(",") if d]


def _matmul_flops(hlo):
    """``{scope: FLOPs}`` of every dot in the module, under the scope of
    the top-level instruction that runs it (its own, or its fusion's)."""
    comps = R._computations(hlo)
    table = _scopes(hlo)

    def own(text, shapes):
        if not re.search(r"\sdot\(", text):
            return 0.0
        lhs = re.search(r"dot\(%?([\w.\-]+)", text).group(1)
        k = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", text).group(1)
        contracted = math.prod(_dims(shapes[lhs])[int(i)]
                               for i in k.split(",") if i)
        return 2.0 * math.prod(_dims(text)) * contracted

    def flops(text, shapes):
        return own(text, shapes) + sum(
            flops(t, dict(comps[c])) for c in R._CALLS.findall(text)
            for _, t in comps[c])

    entry = next(c for c in comps if c.startswith("main"))
    shapes = dict(comps[entry])
    out = defaultdict(float)
    for name, text in comps[entry]:
        f = flops(text, shapes)
        if f:
            out[table[name]] += f
    return out


def test_each_stage_scope_holds_its_matmuls_flop_floor(adatopk):
    """A scope's device time can fall below its FLOPs at the chip's peak
    only if its matmuls are counted elsewhere.  Every stage's backward
    holds twice its forward's matmul FLOPs (the gradients of both
    operands); the stages hold at least the model's FLOPs as the
    benchmark counts them (``chipbench.arith``); ``optim`` holds none."""
    job, hlo = adatopk
    by = _matmul_flops(hlo)
    assert not any(s is None or s.kind != S.STAGE for s in by), by
    fwd = {s.where: f for s, f in by.items() if s.direction == "fwd"}
    bwd = {s.where: f for s, f in by.items() if s.direction == "bwd"}
    assert fwd and set(fwd) <= set(bwd)
    for where, f in fwd.items():
        assert bwd[where] == pytest.approx(2 * f), where
    cfg = resolve("gpt2-xl").smoke
    model = arith.forward_flops(
        {"n_embd": cfg.d_model, "n_head": cfg.n_heads, "n_inner": cfg.d_ff,
         "n_layer": cfg.n_layers, "vocab_size": cfg.vocab}, BATCH, SEQ)
    assert sum(fwd.values()) >= model
    assert sum(bwd.values()) >= 2 * model


def test_live_step_scopes_finds_the_module_that_ran():
    @jax.jit
    def f(scoped_toy_input):     # an argument name no other module has
        x = scoped_toy_input
        with jax.named_scope(S.stage_scope(0, backward=False)):
            y = x @ x
        with jax.named_scope(S.OPTIM):
            return y - x

    compiled = f.lower(jnp.ones((8, 8))).compile()
    names = set(_scopes(compiled.as_text()))
    table = R.live_step_scopes(names)
    assert set(table) == names
    assert {str(s) for s in table.values() if s} == {"rad/s0/fwd", "optim"}
    assert R.live_step_scopes({"no-such-instruction.12345"}) is None


def test_compile_cache_keys_entries_with_metadata(monkeypatch):
    seen = {}
    monkeypatch.setattr(cache.jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-cache")
    assert cache.enable_compile_cache() == "/nonexistent-cache"
    assert seen == {"jax_compilation_cache_include_metadata_in_key": True}


# ---------------------------------------------------------- host spans --
def _host_spans(tmp_path, body):
    """(name, start, end) of every ``train.*`` and ``region.*`` host span
    that ``body`` leaves in a profiler trace."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        body()
    xplane = next(Path(tmp_path).rglob("*.xplane.pb"))
    prof = ProfileData.from_file(str(xplane))
    return sorted((e.name, e.start_ns, e.end_ns)
                  for plane in prof.planes if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith(("train.", "region.")))


def test_train_fusion_steps_are_host_spans(adatopk, tmp_path):
    job, _ = adatopk
    data = _data(resolve("gpt2-xl").smoke)
    losses = []
    spans = _host_spans(tmp_path, lambda: losses.extend(
        train_fusion(job, data, 2)))
    assert len(losses) == 2
    steps = [s for s in spans if s[0] == "train.step"]
    assert len(steps) == 2
    for _, t0, t1 in steps:
        inner = sorted(n for n, s, e in spans
                       if n != "train.step" and t0 <= s and e <= t1)
        assert inner == ["train.batch", "train.dispatch", "train.fetch"]
    assert len(spans) == 8


def test_enabled_region_is_a_host_span_and_disabled_emits_nothing(tmp_path):
    on, off = TraceRecorder(), TraceRecorder(enabled=False)

    def body():
        with on.region(CAT_ENCODE, "region.on", "stage0"):
            pass
        with off.region(CAT_ENCODE, "region.off", "stage0"):
            pass

    spans = _host_spans(tmp_path, body)
    assert [n for n, _, _ in spans] == ["region.on"]
    assert [e.name for e in on.events()] == ["region.on"]
    assert off.events() == []
