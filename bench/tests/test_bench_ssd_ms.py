"""``ssd_ms.score``: device ms per prompt under ``contract.ssd.*``."""
import _paths  # noqa: F401

import pytest

from benchlib import counts, device, readers, scopes, spec

MS = 1_000_000  # ns


def _prompts(scope_of_third):
    """Two 30 ms prefills: a gemm, an op under ``block.ssm`` and a third
    op under ``scope_of_third``, of 10 ms each."""
    ops, names = [], []
    for start in (0, 30 * MS):
        for i, scope in enumerate(("contract.gemm.pallas", "block.ssm",
                                   scope_of_third)):
            ops.append([start + i * 10 * MS, start + (i + 1) * 10 * MS,
                        f"custom-call.{i}", "gemm"])
            names.append(scope)
    return scopes.Scoped.from_json({
        "window": [0, 60 * MS], "ops": [ops],
        "modules": [[[0, 30 * MS, "jit_prefill_step(1)"],
                     [30 * MS, 60 * MS, "jit_prefill_step(1)"]]],
        "spans": [[0, 60 * MS, "client"]], "scopes": [names],
        "program_spans": []})


def _read(trace, kind="score"):
    ctx = readers.Context(kind=kind, trace=trace,
                          peaks=device.PEAKS["TPU v5 lite"],
                          unit_work=(counts.Work(), counts.Work()), units=2)
    return spec.metric_reader("ssd_ms.score")(ctx)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_reads_the_ssd_dispatch_scope(backend):
    assert _read(_prompts(f"contract.ssd.{backend}")) == pytest.approx(10.0)


def test_reads_nothing_where_the_program_has_no_ssd_scope():
    # the parent's program: the chain's ops sit under block.ssm
    assert _read(_prompts("block.ssm")) is None
    assert _read(_prompts("contract.ssd.pallas"), kind="generate") is None


def test_the_kernel_is_classed_with_the_contractions():
    """The kernel's custom call takes its implementation's name, which the
    trace's reader classes ``gemm``: its time lands where ``counts``
    counts the branch's two contractions."""
    from benchlib import trace
    from repro.core import lowering
    name = lowering._pallas_gemm_ssd_impl.__name__
    assert trace.classify(
        f"%{name}.7 = bf16[64,1,256,1536] custom-call") == "gemm"
