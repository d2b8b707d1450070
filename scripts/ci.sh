#!/usr/bin/env bash
# CI entry point: tier-1 tests + a fast dgemm benchmark smoke.
#
#   scripts/ci.sh            # full tier-1 + smoke
#   SKIP_BENCH=1 scripts/ci.sh   # tests only
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== invariant checker: AST rules (repro.analysis) =="
# The import-alias-aware AST pass owns every source-level contract this
# script used to string-match: facility purity (any spelling of
# dot/einsum/matmul, aliased imports, x.dot(y) method calls, the @
# operator), lax purity, grid-owns-batch, attn-is-an-op-class, pack-once,
# plus layer stratification, deprecated-shim usage, mutable default
# arguments, and overbroad excepts.  Rule catalog: DESIGN.md section 10;
# suppressions: `# repro: allow(<rule-id>)` at the flagged line.
python -m repro.analysis src --json lint_report.json
echo "AST invariants OK (lint_report.json)"

echo "== invariant checker: jaxpr contract audit =="
# Traces every registered (op-class, ger, backend) lowering from the
# registry (Pallas in interpret mode — nothing executes) and audits the
# traced program: accumulator-dtype discipline on every dot_general,
# zero-relayout between PackedOperand inputs and their pallas_call, no
# pre-masked HBM operands feeding a kernel, and the static VMEM-residency
# bound over the autotune candidate space.
python -m repro.analysis --jaxpr-only
echo "jaxpr invariants OK"

echo "== tier-1 tests =="
# tests/conftest.py escalates the deprecated shims' DeprecationWarnings to
# errors for in-repo (repro.*) callers.
python -m pytest -x -q

echo "== fault-matrix smoke (<240s) =="
# The serving loop under a seeded fault schedule — one scenario per fault
# kind (kernel raise, NaN poison, page exhaustion, latency spike, step
# crash, transient alloc failure, and sdc: a finite bit-flip on a gemm
# dispatch that only ABFT checksum verification can see).  Each scenario
# must serve every request exactly once (no drops, no duplicates) with
# the KV page pool fully reclaimed — and the sdc scenario must report
# abft_detections > 0; the runner exits nonzero otherwise.
timeout 240 python -m repro.launch.serve --arch mamba2-130m --reduced \
    --batch 2 --prompt-len 8 --gen 6 --requests 4 --fault-matrix

echo "== examples: pipelined MLP + reduced end-to-end train (<420s) =="
# The rebuilt GPipe pipeline (fused vs chunked-with-progress vs sequential
# reference, plus a pallas-backed stage) on 4 forced host devices, and
# the end-to-end trainer at the CI-reduced arch with live step progress.
timeout 180 python examples/pipeline_parallel.py
timeout 240 python examples/train_100m.py --reduced --steps 30 \
    --batch 2 --seq 64 --progress-every 10 --ckpt "$(mktemp -d)/ckpt"

if [[ "${SKIP_BENCH:-0}" != "1" ]]; then
    echo "== dgemm benchmark smoke (<120s) =="
    timeout 120 python -m benchmarks.run --only dgemm --json BENCH_dgemm.json
    python - <<'EOF'
import json
blob = json.load(open("BENCH_dgemm.json"))
rows = {r["name"]: r["derived"] for r in blob["benchmarks"]}
assert not blob["failed"], blob["failed"]
for n in (128, 256, 512, 1024, 2048):
    d = rows[f"dgemm_N{n}"]
    assert d["v5e_util_autotuned"] >= d["v5e_util_heuristic"], (n, d)
print("BENCH_dgemm.json OK: autotuned >= heuristic on every N")
for n in (128, 256):
    d = rows[f"bgemm_B8_N{n}"]
    # vmapped-vs-grid-native columns must both be present and the
    # projection must charge the vmapped trace its extra kernel launches.
    assert d["us_vmapped"] > 0 and d["us_grid_native"] > 0, (n, d)
    assert d["v5e_util_grid_native"] > d["v5e_util_vmapped"], (n, d)
print("BENCH_dgemm.json OK: batched sweep tracks grid-native vs vmapped")
for n in (128, 256):
    d = rows[f"pgemm_N{n}"]
    # the prepacked panel stream must be bitwise-identical to natural
    # layout and both columns must be present (the pack-once contract).
    assert d["bitwise_equal"] == 1, (n, d)
    assert d["us_natural"] > 0 and d["us_packed"] > 0, (n, d)
print("BENCH_dgemm.json OK: packed sweep bitwise-equal to natural layout")
for n in (128, 256):
    d = rows[f"sgemm_N{n}"]
    # the mesh-native sharded dispatch must return the identical bytes,
    # and the collective fault-point count must prove the shard_map
    # actually engaged (not a silently-degraded single-device run)
    assert d["bitwise_equal"] == 1, (n, d)
    assert d["collective_fired"] >= 1, (n, d)
    assert d["us_single"] > 0 and d["us_sharded"] > 0, (n, d)
print("BENCH_dgemm.json OK: sharded sweep bitwise-equal with live collective")
for n in (128, 256):
    d = rows[f"abft_gemm_N{n}"]
    # the checksum-verified dispatch must return the identical bytes and
    # report its detection tax against the plain eager dispatch
    assert d["bitwise_equal"] == 1, (n, d)
    assert d["us_abft_on"] > 0 and d["us_abft_off"] > 0, (n, d)
    assert "overhead_pct" in d, (n, d)
print("BENCH_dgemm.json OK: abft rows bitwise-equal with overhead tracked")
EOF

    echo "== attention benchmark smoke (<120s) =="
    timeout 120 python -m benchmarks.run --only attention \
        --json BENCH_attention.json
    python - <<'EOF'
import json
blob = json.load(open("BENCH_attention.json"))
rows = {r["name"]: r["derived"] for r in blob["benchmarks"]}
assert not blob["failed"], blob["failed"]
for s in (256, 512):
    d = rows[f"flashattn_S{s}"]
    # the causal-bounded grid must issue strictly fewer steps than the
    # rectangular grid and never project worse utilization
    assert d["grid_steps_bounded"] < d["grid_steps_full"], (s, d)
    assert d["v5e_util_bounded"] >= d["v5e_util_full_grid"], (s, d)
    assert d["us_bounded"] > 0 and d["us_full_grid"] > 0, (s, d)
    b = rows[f"attnback_S{s}"]
    assert b["us_flash"] > 0 and b["us_chunked_xla"] > 0, (s, b)
print("BENCH_attention.json OK: bounded grid < full grid on every S")
EOF

    echo "== moe dispatch benchmark smoke (<180s) =="
    timeout 180 python -m benchmarks.run --only moe_dispatch \
        --json BENCH_moe_dispatch.json
    python - <<'EOF'
import json
blob = json.load(open("BENCH_moe_dispatch.json"))
rows = {r["name"]: r["derived"] for r in blob["benchmarks"]}
assert not blob["failed"], blob["failed"]
d = rows["moe_dispatch"]
# the all-to-all exchange dispatch is a pure slot permutation: bitwise
# against the replicated gather path, with the expert ownership split
# across the model axis
assert d["bitwise_equal"] == 1, d
assert d["experts_axis"] > 1, d
assert d["n_experts"] == d["experts_axis"] * d["experts_per_device"], d
assert d["us_gather"] > 0 and d["us_exchange"] > 0, d
print("BENCH_moe_dispatch.json OK: exchange dispatch bitwise-equal to gather")
EOF

    echo "== serving benchmark smoke (<300s) =="
    timeout 300 python -m benchmarks.run --only serving \
        --json BENCH_serving.json
    python - <<'EOF'
import json
blob = json.load(open("BENCH_serving.json"))
rows = {r["name"]: r["derived"] for r in blob["benchmarks"]}
assert not blob["failed"], blob["failed"]
for name in ("serve_decode", "serve_guarded", "serve_prepacked"):
    d = rows[name]
    # every row reports steady-state decode throughput and completes the
    # full request set; the prepacked run must not drop or corrupt work.
    assert d["decode_tok_s"] > 0, (name, d)
    assert d["completed"] == 8, (name, d)
    assert d["decode_tokens"] > 0, (name, d)
d = rows["serve_abft"]
# the checksum-verified row runs a smaller request set (eager decode);
# it must still complete all of it with live decode throughput
assert d["decode_tok_s"] > 0, d
assert d["completed"] == 2, d
print("BENCH_serving.json OK: prepacked + abft serving complete with live decode tok/s")
EOF
fi
