"""The program's own names in a trace: each op's innermost program scope,
read from the HLO the trace holds, the program's host spans, and the
readers of device time by scope and of the trainer loop's idle time."""
import _paths  # noqa: F401

import json
import pathlib

import pytest

from benchlib import counts, device, jobs, readers, scopes, spec, trace

MS = 1_000_000  # ns
DATA = pathlib.Path(__file__).parent / "data"
PEAKS = {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/while/body/closed_call/block.ssm/ssm.state/mul",
     "ssm.state"),
    ("jit(step)/while/body/closed_call/block.ssm/contract.gemm.pallas/"
     "jit(_pallas_gemm_impl)/pallas_call", "contract.gemm.pallas"),
    ("jit(train_step)/transpose(jvp(block.ssm))/contract.conv.xla/"
     "conv_general_dilated", "contract.conv.xla"),
    ("jit(train_step)/transpose(jvp(loss))/mul", "loss"),
    ("jit(step)/while/body/dynamic_update_slice", None),
    ("", None),
    ("jit(f)/blocks/mul", None),
])
def test_innermost_program_scope(op_name, scope):
    assert scopes.innermost(op_name) == scope


def _hand_made():
    # window 0..100 ms; serve_step runs whole at 10-40 and 50-80; at 84-99
    # a third runs whole, but the trace recorded only its first op (the
    # profiler stopped recording ops at 86); a fourth, 101-120, runs past
    # the window; a while container spans 10-40
    return scopes.Scoped.from_json({
        "window": [0, 100 * MS],
        "ops": [[[10 * MS, 40 * MS, "%while = (s32[]) while", "container"],
                 [10 * MS, 20 * MS, "custom-call.1", "gemm"],
                 [20 * MS, 30 * MS, "fusion.2", "other"],
                 [30 * MS, 40 * MS, "copy.3", "other"],
                 [50 * MS, 60 * MS, "custom-call.4", "gemm"],
                 [60 * MS, 75 * MS, "fusion.5", "other"],
                 [75 * MS, 80 * MS, "fusion.6", "other"],
                 [84 * MS, 86 * MS, "fusion.7", "other"]]],
        "modules": [[[10 * MS, 40 * MS, "jit_serve_step(1)"],
                     [50 * MS, 80 * MS, "jit_serve_step(1)"],
                     [84 * MS, 99 * MS, "jit_serve_step(1)"],
                     [101 * MS, 120 * MS, "jit_serve_step(1)"]]],
        "spans": [[0, 100 * MS, "decode"]],
        "scopes": [[None, "contract.gemm.pallas", "ssm.state", None,
                    "contract.gemm.pallas", None, "head", None]],
        "program_spans": [[0, 100 * MS, "repro.trainer.step"],
                          [40 * MS, 45 * MS, "repro.trainer.batch"],
                          [45 * MS, 50 * MS, "repro.trainer.dispatch"],
                          [50 * MS, 85 * MS, "repro.trainer.wait"],
                          [85 * MS, 95 * MS, "repro.trainer.log"],
                          [96 * MS, 97 * MS, "repro.other"]],
    })


def _ctx(kind, r, units=2):
    return readers.Context(kind=kind, trace=r, peaks=PEAKS,
                           unit_work=(counts.Work(), counts.Work()),
                           units=units)


def test_device_time_by_scope_on_the_hand_made_trace():
    r = _hand_made()
    seconds, runs = r.by_scope("serve_step")
    assert runs == 2
    assert seconds == {"contract.gemm.pallas": pytest.approx(0.020),
                       "ssm.state": pytest.approx(0.010),
                       None: pytest.approx(0.025),
                       "head": pytest.approx(0.005)}
    ctx = _ctx("generate", r)
    # (10 + 10) ms of contract.* ops over two executions
    assert scopes.contract_ms(ctx, "generate", "serve_step") == \
        pytest.approx(10.0)
    # copy.3 (10 ms) and fusion.5 (15 ms); the container and the cut-off
    # execution's fusion.7 are left out
    assert scopes.unscoped_ms(ctx, "generate", "serve_step") == \
        pytest.approx(12.5)
    assert scopes.contract_ms(ctx, "score", "serve_step") is None
    assert scopes.contract_ms(ctx, "generate", "prefill_step") is None


def test_trainer_idle_on_the_hand_made_trace():
    # busy 10-40, 50-80, 84-86; idle 0-10, 40-50, 80-84, 86-100; the
    # trainer's own spans (step aside) cover 40-95: 10 + 4 + 9 ms idle
    # over 2 steps
    ctx = _ctx("train", _hand_made())
    assert scopes.trainer_idle_ms(ctx) == pytest.approx(11.5)
    assert scopes.trainer_idle_ms(_ctx("generate", _hand_made())) is None


def test_a_program_without_names_reads_nothing():
    r = _hand_made()
    # another program of the trace may carry names (a persistent cache can
    # hand a process another checkout's executable); serve_step carries none
    r.scopes = [[None] * 7 + ["serve.handoff"]]
    r.program_spans = []
    gen, train = _ctx("generate", r), _ctx("train", r)
    assert scopes.contract_ms(gen, "generate", "serve_step") is None
    assert scopes.unscoped_ms(gen, "generate", "serve_step") is None
    assert scopes.trainer_idle_ms(train) is None


RECORDED = DATA / "score-2k.trace.json"

# What the accepted readers gave on the recorded score-2k trace (three
# prompts, ``units`` 3) before the program named its work.
SCORE_2K = {
    "step_ms.score": 103.31195100000001,
    "mfu.score": 33.622616838051954,
    "gemm_roofline.score": 67.35721952040156,
    "attn_roofline.score": 8.24898688691053,
    "idle_share.score": 0.40132237329981724,
}


def _score_ctx(r):
    cell = spec.load_cell("deepseek-7b-pp4.score-2k")
    model = jobs.make_model(cell.config, False)
    return readers.Context(kind="score", trace=r,
                           peaks=device.PEAKS["TPU v5 lite"],
                           unit_work=counts.score_prompt(model.cfg, 2048),
                           units=3)


def test_recorded_trace_without_scopes_reads_as_before():
    with open(RECORDED) as f:
        d = json.load(f)
    plain, scoped = trace.Reduced.from_json(d), scopes.Scoped.from_json(d)
    assert scoped.scopes is None and scoped.program_spans is None
    assert scoped.ops == plain.ops
    for _, _, name, cls in scoped.ops[0]:
        assert trace.classify(name) == cls
    names = [m["name"] for m in
             json.loads((spec.ROOT / "BENCHMARK.json").read_text())[
                 "per_layer"]]
    for r in (plain, scoped):
        ctx = _score_ctx(r)
        got = {n: spec.metric_reader(n)(ctx) for n in names}
        assert {n: v for n, v in got.items() if v is not None} == SCORE_2K
    assert scoped.breakdown() == plain.breakdown()
    assert scoped.idle_gaps() == plain.idle_gaps()


def test_save_small_cuts_ops_and_scopes_together(tmp_path):
    path = tmp_path / "small.json"
    scopes.save_small(_hand_made(), str(path), 45 * MS, 85 * MS)
    with open(path) as f:
        small = scopes.Scoped.from_json(json.load(f))
    assert small.window == (45 * MS, 85 * MS)
    assert [o[2] for o in small.ops[0]] == ["custom-call.4", "fusion.5",
                                            "fusion.6", "fusion.7"]
    assert small.scopes == [["contract.gemm.pallas", None, "head", None]]
    assert [s[2] for s in small.program_spans] == [
        "repro.trainer.step", "repro.trainer.dispatch",
        "repro.trainer.wait"]
    assert small.spans == [[45 * MS, 85 * MS, "decode"]]


def test_a_cpu_trace_holds_the_program_names(tmp_path):
    """The profiler's trace carries each program's optimized HLO; its
    instructions' op_names give their scopes, and the host spans of the
    program and of the harness are on one clock."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("block.ssm"):
            with jax.named_scope("contract.gemm.xla"):
                y = x @ w

            def body(c, _):
                with jax.named_scope("ssm.state"):
                    c = c * 0.5 + 1.0
                return c, c.sum()
            return jax.lax.scan(body, jnp.tanh(y), None, length=3)

    g = jax.jit(f)
    x = jnp.ones((16, 16))
    jax.block_until_ready(g(x, x))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + "decode"):
            with jax.profiler.TraceAnnotation("repro.trainer.wait"):
                jax.block_until_ready(g(x, x))
    finally:
        jax.profiler.stop_trace()
    path = str(next(tmp_path.glob("**/*.xplane.pb")))
    protos = scopes.hlo_protos(path)
    name = next(n for n in protos if n.startswith("jit_f"))
    table = scopes.instruction_scopes(protos[name])
    assert {"contract.gemm.xla", "ssm.state"} <= set(table.values())
    assert None in table.values()     # the scan's own bookkeeping
    scoped = scopes.Scoped.attach(trace.Reduced.from_xplane(path), path)
    assert scoped.ops == [] and scoped.scopes == []
    assert [s[2] for s in scoped.program_spans] == ["repro.trainer.wait"]
    assert scoped.spans[0][2] == "decode"


def _recorded_scoped(name):
    """A chip run of the cell, traced with the program's scopes and spans
    and cut by ``scopes.save_small``: three decode steps of
    ``mamba2-130m.gen-b64``; one step of ``mamba2-130m.train-2k`` with the
    host's hand-over on either side."""
    with open(DATA / f"{name}.trace.json") as f:
        return scopes.Scoped.from_json(json.load(f))


def test_recorded_decode_steps_by_scope():
    r = _recorded_scoped("gen-b64")
    by_name = {}
    for (_, _, name, _), scope in zip(r.ops[0], r.scopes[0]):
        by_name.setdefault(name.split(" = ")[0], set()).add(scope)
    # the scan's stacking of the whole f32 state, and XLA's copy of it,
    # lie outside every scope of the program; its bf16 cast is ssm.state
    assert by_name["%copy.97"] == {None}
    assert by_name["%bitcast_dynamic-update-slice_fusion.2"] == {None}
    assert by_name["%fusion.56"] == {"ssm.state"}
    kernels = {n: s for n, s in by_name.items() if "_pallas_" in n}
    assert kernels["%_pallas_depthwise_impl.3"] == {"contract.conv.pallas"}
    assert all(s == {"contract.gemm.pallas"} for n, s in kernels.items()
               if "gemm" in n) and len(kernels) == 6
    seconds, runs = r.by_scope("serve_step")
    assert runs == 3
    step = sum(r.module_seconds("serve_step")) / runs
    assert sum(seconds.values()) / runs == pytest.approx(step, rel=0.01)
    ctx = _ctx("generate", r, units=1)
    assert 5.0 < scopes.contract_ms(ctx, "generate", "serve_step") < 6.5
    assert 8.0 < scopes.unscoped_ms(ctx, "generate", "serve_step") < 9.5
    assert scopes.trainer_idle_ms(_ctx("train", r, units=1)) is None


def test_recorded_train_step_and_trainer_spans():
    r = _recorded_scoped("train-2k")
    names = {n for _, _, n in r.program_spans}
    assert names == {"repro.trainer." + n for n in
                     ("step", "batch", "dispatch", "wait", "log")}
    ctx = _ctx("train", r, units=1)
    assert 150 < scopes.contract_ms(ctx, "train", "train_step") < 250
    assert 0 < scopes.unscoped_ms(ctx, "train", "train_step") < 100
    # the chip waits almost only while the trainer loop is in a span of
    # its own
    idle = r.window_s() - r.busy_s()
    assert 0.9 * idle < r.program_idle_s(scopes.TRAINER) <= idle
    assert 2 < scopes.trainer_idle_ms(ctx) < 6


# What the readers gave on the recorded traces before they took the run's
# chip count; with ``chips`` 1 they give the same, to the last bit.  The
# gen-b64 trace holds three decode steps, read as three units of a decode
# step's contractions; the train-2k trace one step.
RECORDED_READINGS = {
    "gen-b64": ("mamba2-130m.gen-b64", 3, {
        "step_ms.gen": 16.472004000000002,
        "mfu.gen": 0.5448701828840086,
        "gemm_roofline.gen": 90.45837166347565,
        "idle_share.gen": 0.012016499043676632,
        "contract_ms.gen": 5.678405333333333,
        "unscoped_ms.gen": 8.720148333333334}),
    "train-2k": ("mamba2-130m.train-2k", 1, {
        "step_ms.train": 467.831401,
        "mfu.train": 15.657023994597608,
        "idle_share.train": 0.9671275173515959,
        "contract_ms.train": 198.20996699999998,
        "unscoped_ms.train": 66.435143,
        "trainer_idle_ms.train": 4.282924}),
    "score-2k": ("deepseek-7b-pp4.score-2k", 3, SCORE_2K),
}


def _unit_work(cell):
    cfg, t = jobs.make_model(cell.config, False).cfg, cell.traffic
    if t["job"] == "generate":
        return (counts.contractions(cfg, t["batch"], 1, head_rows=t["batch"]),
                counts.Work())
    if t["job"] == "score":
        return counts.score_prompt(cfg, t["prompt_len"])
    return counts.train_step(cfg, t["batch"], t["seq"]), counts.Work()


@pytest.mark.parametrize("fixture", sorted(RECORDED_READINGS))
def test_recorded_traces_read_as_before_on_one_chip(fixture):
    name, units, want = RECORDED_READINGS[fixture]
    cell = spec.load_cell(name)
    r = scopes.Scoped.from_json(json.loads(
        (DATA / f"{fixture}.trace.json").read_text()))
    for _, _, op, cls in r.ops[0]:
        assert trace.classify(op) == cls
    ctx = readers.Context(kind=cell.traffic["job"], trace=r,
                          peaks=device.PEAKS["TPU v5 lite"],
                          unit_work=_unit_work(cell), units=units, chips=1)
    got = {m["name"]: spec.metric_reader(m["name"])(ctx)
           for m in cell.per_layer}
    assert {n: v for n, v in got.items() if v is not None} == want


def test_an_execution_the_profiler_cut_short_is_left_out():
    # two prefills of 30 ms, then one the profiler's stop cut to 1 ms,
    # which its one recorded op fills
    r = scopes.Scoped.from_json({
        "window": [0, 100 * MS],
        "ops": [[[0, 20 * MS, "custom-call.1", "gemm"],
                 [20 * MS, 30 * MS, "all-gather.2", "collective"],
                 [30 * MS, 50 * MS, "custom-call.1", "gemm"],
                 [50 * MS, 60 * MS, "all-gather.2", "collective"],
                 [60 * MS, 61 * MS, "custom-call.1", "gemm"]]],
        "modules": [[[0, 30 * MS, "jit_prefill_step(1)"],
                     [30 * MS, 60 * MS, "jit_prefill_step(1)"],
                     [60 * MS, 61 * MS, "jit_prefill_step(1)"]]],
        "spans": [[0, 100 * MS, "client"]],
        "scopes": [["contract.gemm.pallas", None, "contract.gemm.pallas",
                    None, "contract.gemm.pallas"]],
        "program_spans": []})
    assert r.executions("prefill_step") == [(0, 30 * MS), (30 * MS, 60 * MS)]
    seconds, runs = r.by_scope("prefill_step")
    assert runs == 2
    ctx = _ctx("score", r)
    assert scopes.contract_ms(ctx, "score", "prefill_step") == \
        pytest.approx(20.0)
    assert r.class_seconds_per_run("prefill_step", "collective") == [
        pytest.approx(0.010), pytest.approx(0.010)]


def test_recorded_four_chip_prompt():
    """One prompt of ``deepseek-7b-tp4.score-2k`` as four v5e chips traced
    it (a chip run of the benchmark, cut by ``scopes.save_small``)."""
    r = _recorded_scoped("score-2k-tp4")
    assert len(r.ops) == 4
    for chip in r.ops:
        for _, _, name, cls in chip:
            assert trace.classify(name) == cls
    by_class = {}
    for _, _, name, cls in r.ops[0]:
        by_class.setdefault(cls, set()).add(name.split()[-1])
    assert by_class["collective"] == {"all-gather", "all-reduce",
                                      "all-to-all"}
    assert by_class["attention"] == by_class["gemm"] == {"custom-call"}
    cell = spec.load_cell("deepseek-7b-tp4.score-2k")
    work = counts.score_prompt(jobs.make_model(cell.config, False).cfg, 2048)

    def ctx(chips):
        return readers.Context(kind="score", trace=r,
                               peaks=device.PEAKS["TPU v5 lite"],
                               unit_work=work, units=1, chips=chips)
    got = {m["name"]: spec.metric_reader(m["name"])(ctx(4))
           for m in cell.per_layer}
    assert got["step_ms.score"] == pytest.approx(130.68, rel=1e-3)
    assert got["collective_ms.score"] == pytest.approx(40.25, rel=1e-3)
    assert got["contract_ms.score"] == pytest.approx(64.07, rel=1e-3)
    assert 0 < got["mfu.score"] < got["gemm_roofline.score"] < 100
    # read as one chip's, the same work would be four times the share
    assert readers.mfu(ctx(1), "score") == pytest.approx(
        4 * got["mfu.score"])
    assert readers.roofline(ctx(1), "score", "gemm") > 100
