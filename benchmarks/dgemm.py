"""Fig. 11 analogue: DGEMM N x 128 @ 128 x N sweep (+ batched sweep).

The paper measures flops/cycle on real silicon.  This container is CPU, so
we report (a) measured CPU wall time of the facility GEMM (XLA path — the
jit'd production lowering), and (b) the *v5e roofline-projected*
utilization of the Pallas kernel's tiling — for both the ``choose_blocks``
heuristic and the ``repro.core.autotune`` winner, so the tuned-vs-static
gap is tracked across PRs.  The projection is the same "% of peak vs
problem size" curve as the paper's Figure 11 (26 flops/cycle = 81% of peak
on POWER10-MMA at N >= 512); the autotuned column must never fall below
the heuristic one (tests/test_autotune.py holds the invariant).

The batched rows (``bgemm_B<b>_N<n>``) track the grid-native-batch win:
the same (B, M, K) x (B, K, N) contraction dispatched as one batched
``pallas_call`` (grid (b, i, j, k)) versus a ``jax.vmap`` of the 2-D
kernel — measured wall clock of both, plus the v5e roofline projection
where the vmapped trace is charged B kernel-launch overheads and the
grid-native launch exactly one.

The packed rows (``pgemm_N<n>``) track the prepacked-layout subsystem
(core/packing.py): the same GEMM with the weight in its kernel-native
panel stream (``y_layout=``, zero per-call relayout) versus natural
layout, both through the interpreted Pallas kernel — wall clock of both
plus a bitwise-equality bit (the packed fringe contract).

The sharded rows (``sgemm_N<n>``) track the mesh-native contract path
(DESIGN.md section 11): the same facility GEMM dispatched single-device
versus sharded M-over-data / N-over-model on a forced 8-way host mesh
(subprocess — the parent's jax is already initialized single-device),
with the bitwise-equality bit, the collective fault-point count proving
the shard_map engaged, and per-shard vs global roofline projections.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import autotune, packing, tiling
from repro.core.precision import Ger, policy
from repro.kernels import ref
from repro.kernels.mma_gemm import mma_gemm
from repro.roofline.analysis import gemm_projected_util


def run():
    rng = np.random.default_rng(0)
    kind = Ger.BF16GER2
    pol = policy(kind)
    for n in (128, 256, 512, 1024, 2048):
        m, k = n, 128
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        f = jax.jit(lambda a, b: ref.ger(a, b, Ger.F32GER))
        us = time_fn(f, x, y)
        flops = 2 * m * n * k
        # v5e projection for the bf16 kernel tiling at this shape:
        # static heuristic vs autotuned winner.
        heur = tiling.choose_blocks(m, n, k, kind)
        tuned = autotune.autotune(kind, m, n, k)
        util_heur = gemm_projected_util(m, n, k, heur, pol)
        util_tuned = gemm_projected_util(m, n, k, tuned, pol)
        emit(f"dgemm_N{n}", us,
             f"cpu_gflops={flops / us / 1e3:.1f};"
             f"v5e_util_heuristic={util_heur:.3f};"
             f"v5e_util_autotuned={util_tuned:.3f};"
             f"block_heuristic={heur.bm}x{heur.bn}x{heur.bk};"
             f"block_autotuned={tuned.bm}x{tuned.bn}x{tuned.bk}")

    # ---- batched sweep: vmapped trace vs grid-native batch ----
    b = 8
    for n in (128, 256):
        m, k = n, 128
        cfg = tiling.choose_blocks(m, n, k, kind)
        blk = (cfg.bm, cfg.bn, cfg.bk)
        xb = jnp.asarray(rng.normal(size=(b, m, k)), jnp.bfloat16)
        yb = jnp.asarray(rng.normal(size=(b, k, n)), jnp.bfloat16)

        grid_native = jax.jit(lambda a, c: mma_gemm(
            a, c, kind=kind, block=blk, interpret=True))
        vmapped = jax.jit(jax.vmap(lambda a, c: mma_gemm(
            a, c, kind=kind, block=blk, interpret=True)))
        us_grid = time_fn(grid_native, xb, yb)
        us_vmapped = time_fn(vmapped, xb, yb)
        util_grid = gemm_projected_util(m, n, k, cfg, pol, b=b, launches=1)
        util_vmap = gemm_projected_util(m, n, k, cfg, pol, b=b, launches=b)
        emit(f"bgemm_B{b}_N{n}", us_grid,
             f"us_grid_native={us_grid:.1f};"
             f"us_vmapped={us_vmapped:.1f};"
             f"v5e_util_grid_native={util_grid:.3f};"
             f"v5e_util_vmapped={util_vmap:.3f};"
             f"block={cfg.bm}x{cfg.bn}x{cfg.bk}")

    # ---- packed sweep: prepacked weight panels vs natural layout ----
    for n in (128, 256):
        m, k = n, 128
        cfg = tiling.choose_blocks(m, n, k, kind)
        blk = (cfg.bm, cfg.bn, cfg.bk)
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.bfloat16)
        lay = packing.GemmLayout(kind=kind, block=blk, side="y",
                                 rows=k, cols=n)
        po = packing.pack_gemm(w, lay)
        natural = jax.jit(lambda a, c: mma_gemm(
            a, c, kind=kind, block=blk, interpret=True))
        packed = jax.jit(functools.partial(
            mma_gemm, kind=kind, y_layout=lay, interpret=True))
        us_nat = time_fn(natural, x, w)
        us_pack = time_fn(packed, x, po.data)
        bitwise = int(bool(
            (np.asarray(natural(x, w)) == np.asarray(packed(x, po.data)))
            .all()))
        emit(f"pgemm_N{n}", us_pack,
             f"us_natural={us_nat:.1f};"
             f"us_packed={us_pack:.1f};"
             f"bitwise_equal={bitwise};"
             f"block={cfg.bm}x{cfg.bn}x{cfg.bk}")

    # ---- sharded sweep: mesh-native contract vs single-device ----
    # The sharded path wants real (forced-host) devices and the parent
    # process's jax is long since initialized single-device, so the probe
    # runs in a subprocess with an 8-way forced host platform and reports
    # one JSON line per shape.  Wall clock on interpreted-Pallas CPU
    # shards is diagnostic only; the row's contract is the bitwise bit
    # plus the per-shard roofline projection (each shard solves the
    # m/dp x n/tp slab with the full K resident).
    import json as _json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    # A forced host mesh by design: the child stays off any accelerator
    # (which this process may hold).
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_PROBE], capture_output=True,
        text=True, env=env, timeout=300)
    if out.returncode != 0:
        raise RuntimeError(f"sharded gemm probe failed:\n{out.stderr}")
    for line in out.stdout.splitlines():
        if not line.startswith("SGEMM "):
            continue
        rec = _json.loads(line[len("SGEMM "):])
        m, n, k = rec["m"], rec["n"], rec["k"]
        dp, tp = rec["dp"], rec["tp"]
        cfg = tiling.choose_blocks(m, n, k, kind)
        util_global = gemm_projected_util(m, n, k, cfg, pol)
        util_shard = gemm_projected_util(m // dp, n // tp, k, cfg, pol)
        emit(f"sgemm_N{n}", rec["us_sharded"],
             f"us_single={rec['us_single']:.1f};"
             f"us_sharded={rec['us_sharded']:.1f};"
             f"bitwise_equal={rec['bitwise_equal']};"
             f"collective_fired={rec['collective_fired']};"
             f"mesh={dp}x{tp};"
             f"v5e_util_global={util_global:.3f};"
             f"v5e_util_per_shard={util_shard:.3f}")

    # ---- abft sweep: checksum-verified dispatch vs plain dispatch ----
    # Both arms run the *eager* facility dispatch (verification needs
    # concrete operands, so there is no jitted abft path to compare
    # against); the delta is the detection tax: the kernel's checksum
    # fold plus the reference colsum/rowsum contractions and the
    # tolerance compare.  Recovery is free until a fault fires.
    import dataclasses

    from repro.core import facility

    for n in (128, 256):
        m, k = n, 128
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        plan = facility.Plan(backend="pallas")
        plain = lambda a, c: facility.contract("mk,kn->mn", a, c,
                                               plan=plan)

        def verified(a, c):
            with facility.configure(dataclasses.replace(
                    facility.current(), guards=True, abft=True)):
                return facility.contract("mk,kn->mn", a, c, plan=plan)

        us_off = time_fn(plain, x, y)
        us_on = time_fn(verified, x, y)
        bitwise = int(bool(
            (np.asarray(plain(x, y)) == np.asarray(verified(x, y)))
            .all()))
        overhead = (us_on - us_off) / us_off * 100.0
        emit(f"abft_gemm_N{n}", us_on,
             f"us_abft_on={us_on:.1f};"
             f"us_abft_off={us_off:.1f};"
             f"overhead_pct={overhead:.1f};"
             f"bitwise_equal={bitwise}")


# The subprocess body for the sharded sweep.  It re-runs the same
# facility.contract under (a) plain single-device dispatch and (b) the
# ambient 2x4 (data, model) mesh rules, where the pallas gemm lowering
# shards M over data and N over model under one shard_map
# (DESIGN.md section 11).  The collective fault probe proves the sharded
# path engaged — a silently-degraded dispatch would time the single-device
# kernel twice and trivially match bitwise.
_SHARDED_PROBE = r'''
import json

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from benchmarks.common import time_fn
from repro.core import facility
from repro.core.lowering import Plan
from repro.parallel import api as par
from repro.runtime import faults

rng = np.random.default_rng(0)
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
rules = par.default_rules(mesh)
plan = Plan(backend="pallas")

for n in (128, 256):
    m, k = n, 128
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)

    def single(a, c):
        return facility.contract("mk,kn->mn", a, c, plan=plan)

    def sharded(a, c):
        with par.use_rules(rules):
            return facility.contract("mk,kn->mn", a, c, plan=plan)

    us_single = time_fn(jax.jit(single), x, y)
    us_sharded = time_fn(jax.jit(sharded), x, y)
    probe = faults.FaultPlan([faults.FaultSpec(
        faults.COLLECTIVE, kind=faults.LATENCY, latency_s=0.0,
        every=1, max_fires=None)])
    with faults.install(probe):
        got = sharded(x, y)
    bitwise = int(bool((np.asarray(single(x, y)) == np.asarray(got)).all()))
    print("SGEMM " + json.dumps({
        "m": m, "n": n, "k": k, "dp": 2, "tp": 4,
        "us_single": us_single, "us_sharded": us_sharded,
        "bitwise_equal": bitwise,
        "collective_fired": len(probe.fired(faults.COLLECTIVE))}))
'''
