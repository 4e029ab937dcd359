"""Device scopes of the GPipe ``shard_map`` step (``pipe/*``,
:mod:`repro.obs.scopes`) as the benchmark reads them from a compiled
module, the step's kernel policy, and the step against the plain
reference.

The step needs four devices, so one subprocess with four forced host
devices compiles a small gpt2-xl pipeline ((pod, model) = (2, 2), one
layer a stage, the pod edge after ``block_1`` compressed) and reports what
the tests below check."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.obs import scopes as S

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent(r'''
    import contextlib, json, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from chipbench import run
    from chipbench.compare import checks, numbers
    from chipbench.session import host_batches
    from chipbench.scope_reduce import _CALLS, _computations, \
        instruction_scopes
    from repro.configs import resolve
    from repro.distributed import pipeline as PL
    from repro.obs.scopes import classify
    from repro.optim import adamw

    out = {}
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pod", "model"))
    cfg = resolve("gpt2-xl").smoke.replace(n_layers=4, max_seq=64)
    N_MICRO, B, SEQ, RATIO = 4, 4, 64, 10.0
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    batch = PL.microbatch({k: jax.random.randint(r, (B, SEQ), 0, cfg.vocab)
                           for k, r in zip(("tokens", "labels"), keys)},
                          N_MICRO)

    @contextlib.contextmanager
    def no_scope(name):
        yield

    def cond_edge(index, branches, x):
        """The edge codec as one ``lax.cond`` on a slow edge leaving the
        stage, the form the step took before it had an edge scope."""
        assert len(branches) == 2
        return jax.lax.cond(index > 0, branches[1], branches[0], x)

    def leaves_equal(a, b):
        return all(bool(np.array_equal(np.asarray(x), np.asarray(y)))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    def strip(hlo):
        """The module's instructions without their metadata."""
        return [re.sub(r", metadata=\{[^}]*\}", "", l)
                for l in hlo.splitlines() if " = " in l and "%" in l]

    with jax.set_mesh(mesh):
        params = PL.place_params(cfg, mesh, jax.random.PRNGKey(0))
        opt = adamw(1e-3)
        state = jax.jit(opt.init)(params)

        def compiled(use_kernel="auto"):
            step = PL.make_pipeline_train_step(cfg, mesh, opt, N_MICRO,
                                               RATIO, use_kernel)
            return jax.jit(step).lower(params, state, batch).compile()

        exe = compiled()
        hlo = exe.as_text()
        loss_grad = jax.jit(jax.value_and_grad(PL.make_pipeline_train_fn(
            cfg, mesh, N_MICRO, RATIO, "auto")))
        scoped = loss_grad(params, batch)
        named_scope, switch = jax.named_scope, jax.lax.switch
        jax.named_scope = no_scope
        try:
            bare_hlo = compiled().as_text()
            bare = jax.jit(jax.value_and_grad(PL.make_pipeline_train_fn(
                cfg, mesh, N_MICRO, RATIO, "auto")))(params, batch)
            jax.lax.switch = cond_edge
            unscoped_cond = jax.jit(jax.value_and_grad(
                PL.make_pipeline_train_fn(cfg, mesh, N_MICRO, RATIO,
                                          "auto")))(params, batch)
        finally:
            jax.named_scope, jax.lax.switch = named_scope, switch
        out["bare_has_pipe"] = "pipe/" in bare_hlo
        out["same_instructions"] = strip(hlo) == strip(bare_hlo)
        out["bit_equal"] = leaves_equal(scoped, bare)
        out["bit_equal_to_cond_form"] = leaves_equal(scoped, unscoped_cond)
        out["loss"] = float(scoped[0])

        def jaxpr(use_kernel):
            step = PL.make_pipeline_train_step(cfg, mesh, opt, N_MICRO,
                                               RATIO, use_kernel)
            return str(jax.make_jaxpr(step)(params, state, batch))
        out["codec_auto"] = "xla_encode_topk" in jaxpr("auto")
        out["codec_default"] = "xla_encode_topk" in jaxpr(False)

    # every instruction of the compiled step, by scope
    table = instruction_scopes(hlo, classify)
    comps = _computations(hlo)
    fused = {c for body in comps.values() for _, t in body
             if re.search(r"\bfusion\(", t) for c in _CALLS.findall(t)}
    shape = re.compile(r"^\(?[a-z]\w*\[([\d,]*)\]")

    def dims(text):
        return [int(d) for d in shape.match(text).group(1).split(",") if d]

    def flops(text, shapes):
        f = 0.0
        if re.search(r"\sdot\(", text):
            lhs = re.search(r"dot\(%?([\w.\-]+)", text).group(1)
            k = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", text)
            f = 2.0 * np.prod(dims(text)) * np.prod(
                [dims(shapes[lhs])[int(i)] for i in k.group(1).split(",")
                 if i])
        return f + sum(flops(t, dict(comps[c]))
                       for c in _CALLS.findall(text) for _, t in comps[c])

    matmul, codec = {}, {}
    for comp, body in comps.items():
        if comp in fused:
            continue
        shapes = dict(body)
        for name, text in body:
            scope = table[name]
            label = None if scope is None else str(scope)
            f = flops(text, shapes)
            if f:
                matmul[label] = matmul.get(label, 0.0) + f
            op = re.search(r'op_name="([^"]*)"', text)
            if op and re.search(r"(encode|decode)_topk", op.group(1)):
                codec[name] = label
    out["matmul_flops"] = matmul
    out["codec_ops"] = codec
    out["labels"] = sorted({str(s) for s in table.values() if s})

    # the run's comparison with the plain reference at a small width
    files = run.ROOT / "chipbench"
    conf = json.loads((files / "configs" / "gpt2-xl-48.json")
                      .read_text())
    conf.update(n_layer=4, n_embd=128, n_head=4, n_positions=64,
                vocab_size=500, name="small-gpipe")
    traffic = json.loads((files / "traffic" / "gpipe4-adatopk.json")
                         .read_text())
    traffic.update(seq=64, distinct_batches=4, micro_batch=2, batch=8,
                   compressed_edges=[{"after": "block_1",
                                      "k_per_block": 14}])
    # set_limits.py's rule on calibrate.py's readings of this small step
    # on the CPU: two sound seeds read at most 2.3e-7 / 6.0e-7 / 2.2e-7,
    # the half batch 7.0e-3 / 0.95 / 0.159
    limits = {"loss_gap": 2.2e-4, "grad_gap": 8.1e-3, "delta_gap": 1.7e-3}
    e2e = [{"name": n, "unit": u} for n, u in (
        ("tokens_per_s", "tokens/s"), ("step_hbm_gb", "GB"),
        ("setup_s", "s"))]
    cell = run.Cell("small-gpipe", 4, conf, traffic, limits, e2e, [])
    seed = 2**31 + 101           # not one of the calibration seeds
    res = run.execute(cell, seed, 0.2, False, jax.devices()[:4])
    out["reference"] = {"correct": res["correct"], "checks": res["checks"],
                        "limits": limits}
    # the planted fault that calibration reads: the loss over half the rows
    host = host_batches(traffic, conf["vocab_size"], seed)[:run.CHECK_STEPS]
    sound, half = (run.reference(cell, jax.devices()[:4], seed, host, v)
                   for v in ("reference", "half_batch"))
    out["half_batch"] = checks(numbers(half, sound), limits)
    print("RESULT " + json.dumps(out))
''')


@pytest.fixture(scope="module")
def step():
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "PYTHONPATH": f"{ROOT / 'src'}:{ROOT}", "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600, cwd=ROOT, env=env)
    line = next((l for l in res.stdout.splitlines()
                 if l.startswith("RESULT ")), None)
    assert line, res.stdout[-3000:] + res.stderr[-3000:]
    return json.loads(line[len("RESULT "):])


def test_every_matmul_falls_in_a_pipe_scope(step):
    labels = set(step["matmul_flops"])
    assert None not in labels and "None" not in labels
    assert labels == {"pipe/blocks/fwd", "pipe/blocks/bwd", "pipe/head/fwd",
                      "pipe/head/bwd"}


def test_backward_is_told_from_forward_by_its_transpose(step):
    """Each block's backward takes the gradients of both operands of every
    matmul (twice the forward's FLOPs) and recomputes the part of the
    forward whose results it needs (per-block remat): more than twice the
    forward, at most three times.  The head is not recomputed: twice."""
    f = step["matmul_flops"]
    assert 2 * f["pipe/blocks/fwd"] < f["pipe/blocks/bwd"] \
        <= 3 * f["pipe/blocks/fwd"]
    assert f["pipe/head/bwd"] == pytest.approx(2 * f["pipe/head/fwd"])


def test_codec_ops_fall_only_in_the_pod_edge_scope(step):
    codec = step["codec_ops"]
    assert codec and set(codec.values()) == {"pipe/edge/block_1/fwd",
                                             "pipe/edge/block_1/bwd"}
    edge = [l for l in step["labels"] if l.startswith("pipe/edge/")]
    assert set(edge) == set(codec.values())
    assert not any(l.startswith("pipe/edge/") for l in step["matmul_flops"])


def test_every_part_of_the_step_has_its_scope(step):
    assert set(step["labels"]) == {
        f"pipe/{p}/{d}" for p in ("embed", "blocks", "head", "exchange",
                                  "ticks", "edge/block_1")
        for d in S.DIRECTIONS} | {S.OPTIM}


def test_scopes_change_metadata_only(step):
    assert not step["bare_has_pipe"]
    assert step["same_instructions"]
    assert step["bit_equal"]
    # one lax.switch branch per slow edge computes what the one lax.cond
    # it replaced did
    assert step["bit_equal_to_cond_form"]


def test_kernel_policy_reaches_the_codec(step):
    """``make_pipeline_train_step`` hands ``use_kernel`` on: ``"auto"``
    runs the blockwise codec (its XLA form off the chip), the default the
    legacy global top-k."""
    assert step["codec_auto"] and not step["codec_default"]


def test_step_matches_the_plain_reference(step):
    ref = step["reference"]
    assert set(ref["checks"]) >= set(ref["limits"]) | {"plan_mismatch"}
    assert ref["correct"], ref["checks"]
    # the same limits catch the half batch
    assert not all(c["ok"] for c in step["half_batch"].values())


@pytest.mark.parametrize("part", S.PIPE_PARTS)
@pytest.mark.parametrize("backward", [False, True])
def test_classify_round_trips_pipe_parts(part, backward):
    op = "jit(step)/" + ("transpose(jvp())" if backward else "jvp()") \
        + f"/shard_map/while/body/{S.pipe_scope(part)}/dot_general"
    got = S.classify(op)
    assert got == S.Scope(part, "", S.DIRECTIONS[backward], S.PIPE)
    assert str(got) == f"pipe/{part}/{S.DIRECTIONS[backward]}"


@pytest.mark.parametrize("op_name, label", [
    ("jit(step)/transpose(jvp())/shard_map/pipe/ticks/while",
     "pipe/ticks/bwd"),
    ("jit(step)/jvp()/shard_map/pipe/ticks/while/body/dynamic_update_slice",
     "pipe/ticks/fwd"),
    ("jit(step)/jvp()/shard_map/pipe/ticks/while/body/closed_call/"
     "pipe/blocks/while/body/dot_general", "pipe/blocks/fwd"),
    ("jit(step)/transpose(jvp())/shard_map/pipe/ticks/while/body/"
     "closed_call/pipe/embed/psum_invariant", "pipe/embed/bwd"),
    ("jit(step)/jvp()/shard_map/pipe/ticks/while/body/switch/branch_1_fun/"
     "pipe/edge/block_23/jit(_encode_pallas)/pallas_call",
     "pipe/edge/block_23/fwd"),
])
def test_a_part_inside_the_tick_loop_takes_the_innermost_scope(op_name,
                                                               label):
    assert str(S.classify(op_name)) == label


@pytest.mark.parametrize("backward", [False, True])
def test_classify_round_trips_the_pipe_edge(backward):
    edge = S.pipe_edge_scope("block_23")
    op = "jit(step)/" + ("transpose(jvp())" if backward else "jvp()") \
        + f"/shard_map/cond/branch_1_fun/{edge}/jit(_encode_pallas)/x"
    got = S.classify(op)
    assert got == S.Scope(S.CODEC, "block_23", S.DIRECTIONS[backward],
                          S.PIPE)
    assert str(got) == f"{edge}/{S.DIRECTIONS[backward]}"


@pytest.mark.parametrize("op_name, label", [
    ("jit(step)/rad/s3/fwd/jvp()/dot_general", "rad/s3/fwd"),
    ("jit(step)/rad/s3/bwd/transpose(jvp())/mul", "rad/s3/bwd"),
    ("jit(step)/rad/edge/head/s14/bwd/jit(_decode_pallas)/pallas_call",
     "rad/edge/head/s14/bwd"),
    ("jit(step)/optim/sub", "optim"),
    ("jit(step)/pipeline/blocks/add", None),
    ("jit(step)/pipe/blocksx/add", None),
    ("jit(step)/pipe/edges/block_1/add", None),
    ("jit(step)/transpose(jvp())/shard_map/while", None),
])
def test_rad_names_classify_as_before_and_near_misses_do_not(op_name, label):
    got = S.classify(op_name)
    assert (None if got is None else str(got)) == label
    if got is not None:
        assert got.step == S.RAD


def test_pipe_scope_names_are_checked():
    with pytest.raises(ValueError):
        S.pipe_scope("codec")
    with pytest.raises(ValueError):
        S.pipe_edge_scope("a/b")
