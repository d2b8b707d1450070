"""The reduction from a trace to busy time, idle share, idle gaps named
by the harness's spans, and the device time of each class of operation."""
import _paths  # noqa: F401

import json
import pathlib

import pytest

from benchlib import readers, trace
from benchlib.counts import Work

MS = 1_000_000  # ns


def _hand_made():
    # window 0..100 ms from the spans; two overlapping gemm operations
    # (10-30, 20-40), a flash kernel 50-60, another op 90-110 (clipped)
    return trace.Reduced.from_json({
        "window": [0, 100 * MS],
        "ops": [[[10 * MS, 30 * MS, "custom-call.1", "gemm"],
                 [20 * MS, 40 * MS, "fusion.2", "gemm"],
                 [50 * MS, 60 * MS, "custom-call.3", "attention"],
                 [90 * MS, 110 * MS, "fusion.4", "other"]]],
        "modules": [[[10 * MS, 40 * MS, "jit_prefill_step"],
                     [50 * MS, 60 * MS, "jit_prefill_step"],
                     [90 * MS, 110 * MS, "jit_prefill_step"]]],
        "spans": [[0, 45 * MS, "prefill"], [45 * MS, 100 * MS, "client"]],
    })


def test_busy_is_the_union_in_the_window():
    r = _hand_made()
    assert r.busy_intervals(0) == [[10 * MS, 40 * MS], [50 * MS, 60 * MS],
                                   [90 * MS, 100 * MS]]
    assert r.busy_s() == pytest.approx(0.050)
    assert r.window_s() == pytest.approx(0.100)


def test_idle_gaps_are_named_by_the_overlapping_span():
    gaps = sorted(_hand_made().idle_gaps())
    assert gaps == [(pytest.approx(0.010), "prefill"),
                    (pytest.approx(0.010), "prefill"),
                    (pytest.approx(0.030), "client")]


def test_class_and_program_seconds():
    r = _hand_made()
    assert r.class_seconds("gemm") == pytest.approx(0.040)
    assert r.class_seconds("attention") == pytest.approx(0.010)
    # the third execution runs past the window and is left out
    assert r.module_seconds("prefill_step") == [pytest.approx(0.030),
                                                pytest.approx(0.010)]


def test_breakdown_lists_the_largest_first():
    b = _hand_made().breakdown()
    assert b["device_ops"][0] == ["custom-call.1", pytest.approx(0.020)]
    assert b["idle_gaps"][0] == ["client", pytest.approx(0.030)]


def test_readers_on_the_hand_made_trace():
    r = _hand_made()
    peaks = {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12}
    work = (Work(2e12, 1e9, ((2e12, 1e9, 1),)),
            Work(0.5e12, 1e6, ((0.5e12, 1e6, 1),)))
    ctx = readers.Context(kind="score", trace=r, peaks=peaks,
                          unit_work=work, units=1)
    assert readers.idle_share(ctx, "score") == pytest.approx(50.0)
    # 2.5e12 FLOP in 0.1 s against 100 TFLOP/s
    assert readers.mfu(ctx, "score") == pytest.approx(25.0)
    # gemm: least 0.02 s over 0.04 s of gemm operations
    assert readers.roofline(ctx, "score", "gemm") == pytest.approx(50.0)
    assert readers.roofline(ctx, "score", "attention") == pytest.approx(50.0)
    assert readers.step_ms(ctx, "score", "prefill_step") == pytest.approx(20)
    # another kind of job reads nothing
    assert readers.mfu(ctx, "train") is None


def test_compact_and_classify_hlo_names():
    gemm = trace.compact(
        "%_pallas_gemm_impl.43 = bf16[2048,4096]{1,0:T(8,128)(2,1)S(1)} "
        "custom-call(bf16[2048,11008]{1,0} %fusion.1), "
        "custom_call_target=\"tpu_custom_call\"")
    assert gemm == "%_pallas_gemm_impl.43 = bf16[2048,4096] custom-call"
    assert trace.classify(gemm) == "gemm"
    assert trace.classify("%_pallas_attn_impl.5 = bf16[1,32,2048,128] "
                          "custom-call") == "attention"
    assert trace.classify("%_pallas_depthwise_impl.3 = bf16[64,1,1,1792] "
                          "custom-call") == "other"
    fused = trace.compact("%fusion.504 = bf16[8,8,24,256,256]{4,3,2,1,0} "
                          "fusion(f32[8] %p), kind=kOutput, calls=%fc.1")
    assert fused == "%fusion.504 = bf16[8,8,24,256,256] fusion kOutput"
    assert trace.classify(fused) == "gemm"
    assert trace.classify("%fusion.56 = bf16[64,24,128,64] fusion "
                          "kLoop") == "other"
    assert trace.classify("%while = (s32[], f32[2]) while") == "container"


RECORDED = pathlib.Path(__file__).parent / "data" / "score-2k.trace.json"


def _recorded():
    """Three prompts of ``deepseek-7b-pp4.score-2k`` as a v5e traced them
    (a chip run of the benchmark, reduced and cut by
    ``trace.save_small``)."""
    with open(RECORDED) as f:
        return trace.Reduced.from_json(json.load(f))


def test_recorded_trace_classes_follow_the_names():
    r = _recorded()
    for _, _, name, cls in r.ops[0]:
        assert trace.classify(name) == cls
    names = {n for _, _, n, c in r.ops[0] if c == "attention"}
    assert names and all("_pallas_attn_impl" in n for n in names)


def test_recorded_trace_busy_idle_and_programs():
    r = _recorded()
    busy, window = r.busy_s(), r.window_s()
    assert 0 < busy <= window
    # three 2048-token prompts keep the chip busy but for the hand-offs
    assert 1 - busy / window < 0.01
    # each prefill_step ran whole inside the window
    steps = r.module_seconds("prefill_step")
    assert len(steps) == 3 and max(steps) - min(steps) < 1e-4
    # the classes are disjoint parts of the busy time
    leaves = r.class_seconds("gemm") + r.class_seconds("attention") + \
        r.class_seconds("other")
    assert leaves <= busy * 1.0001
    # the gaps lie where the host handed work over
    gaps = r.idle_gaps()
    assert sum(g for g, _ in gaps) == pytest.approx(window - busy)
    assert {who for _, who in gaps} <= {"prefill", "client"}


@pytest.mark.parametrize("name,cls", [
    ("%all-gather-start.3 = (bf16[512,4096], bf16[2048,4096]) "
     "all-gather-start", "collective"),
    ("%all-gather-done.3 = bf16[2048,4096] all-gather-done", "collective"),
    ("%all-gather.54 = bf16[1,2048,4096] all-gather", "collective"),
    ("%all-reduce.1 = bf16[1,2048,4096] all-reduce", "collective"),
    ("%reduce-scatter.2 = bf16[512,4096] reduce-scatter", "collective"),
    ("%all-to-all.33 = bf16[1,2752,4,1024] all-to-all", "collective"),
    ("%collective-permute-done.1 = bf16[8] collective-permute-done",
     "collective"),
    ("%all-reduce-fusion.2 = f32[2048] fusion kLoop", "collective"),
    ("%fusion.56 = bf16[64,24,128,64] fusion kLoop", "other"),
    ("%reduce.4 = f32[2048] reduce", "other"),
    ("%_pallas_gemm_impl.43 = bf16[2048,4096] custom-call", "gemm"),
    ("%fusion.504 = bf16[8,8,24,256,256] fusion kOutput", "gemm"),
    ("%_pallas_attn_impl.5 = bf16[1,32,2048,128] custom-call", "attention"),
])
def test_classify_collectives(name, cls):
    assert trace.classify(name) == cls


def _chips(n, work_ms):
    """A prefill of ``work_ms`` ms of gemm split over ``n`` chips: each
    chip runs its share in ``work_ms / n`` ms, then 1 ms of collectives
    (start 0.4, done 0.6), inside one execution; the window fits it."""
    t = int(work_ms / n * MS)
    chip_ops = [[0, t, "%_pallas_gemm_impl.1 = bf16[8] custom-call", "gemm"],
                [t, t + 4 * MS // 10, "%all-gather-start.1 = bf16[8] "
                 "all-gather-start", "collective"],
                [t + 4 * MS // 10, t + MS, "%all-gather-done.1 = bf16[8] "
                 "all-gather-done", "collective"]]
    return trace.Reduced.from_json({
        "window": [0, t + MS],
        "ops": [list(chip_ops) for _ in range(n)],
        "modules": [[[0, t + MS, "jit_prefill_step"]] for _ in range(n)],
        "spans": [[0, t + MS, "prefill"]]})


def test_shares_of_a_peak_are_per_chip():
    peaks = {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12}
    work = (Work(2e12, 1e9, ((2e12, 1e9, 1),)), Work())
    one, four = _chips(1, 40), _chips(4, 160)
    ctx1 = readers.Context(kind="score", trace=one, peaks=peaks,
                           unit_work=work, units=1)
    # four times the work, split four ways, in the same time a chip
    work4 = (work[0] * 4, Work())
    ctx4 = readers.Context(kind="score", trace=four, peaks=peaks,
                           unit_work=work4, units=1, chips=4)
    assert readers.mfu(ctx4, "score") == pytest.approx(
        readers.mfu(ctx1, "score"))
    assert readers.roofline(ctx4, "score", "gemm") == pytest.approx(
        readers.roofline(ctx1, "score", "gemm"))
    # 2e12 FLOP in 41 ms against 100 TFLOP/s; 20 ms least over 40 of gemm
    assert readers.mfu(ctx1, "score") == pytest.approx(100 * 20 / 41)
    assert readers.roofline(ctx1, "score", "gemm") == pytest.approx(50.0)


def test_collective_ms_per_execution():
    r = _chips(4, 160)
    ctx = readers.Context(kind="score", trace=r, peaks={}, unit_work=(
        Work(), Work()), units=1, chips=4)
    assert r.class_seconds_per_run("prefill_step", "collective") == [
        pytest.approx(0.001)]
    assert readers.class_ms(ctx, "score", "prefill_step",
                            "collective") == pytest.approx(1.0)
    # a trace with no collective reads nothing
    assert readers.class_ms(ctx, "score", "prefill_step",
                            "attention") is None
    assert readers.class_ms(ctx, "generate", "prefill_step",
                            "collective") is None
