"""MoE expert dispatch: all-to-all exchange vs replicated gather.

The mesh-native MoE layer (``models/moe.py`` with ``EXCHANGE_DISPATCH``)
routes the capacity-bucketed token slabs through
``parallel.api.expert_exchange``: an ``all_to_all`` scatters each
device's slots to the experts' owners, the expert FFN contracts run on
local experts only, and the inverse exchange brings the outputs home — a
pure slot permutation, so the result is *bitwise* equal to the
annotation-only gather path where every device computes all experts.

This benchmark times both dispatch modes end-to-end (reduced mixtral
arch, 8 experts over a 4-way model axis) in a subprocess with a forced
8-way host platform, and emits one ``moe_dispatch`` row: wall clock of
both modes, the bitwise bit, and the exchanged-slot geometry.  On CPU
the exchange shows as overhead (the collective is a copy); the row's
contract is equality plus the per-device expert count — on a real fleet
the same geometry divides the FFN flops by the axis size.
"""

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_PROBE = r'''
import json

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from benchmarks.common import time_fn
from repro.configs import get
from repro.configs.base import reduced
from repro.models import moe as MOE
from repro.parallel import api as par

cfg = reduced(get("mixtral-8x22b"))
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
rules = par.default_rules(mesh)

p = MOE.init_moe(jax.random.key(0), cfg)
x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model), jnp.float32)

def gather(params, xin):
    out, _ = MOE.apply_moe(params, xin, cfg)
    return out

def exchange(params, xin):
    MOE.EXCHANGE_DISPATCH = True
    try:
        with par.use_rules(rules):
            out, _ = MOE.apply_moe(params, xin, cfg)
    finally:
        MOE.EXCHANGE_DISPATCH = False
    return out

us_gather = time_fn(gather, p, x)
us_exchange = time_fn(exchange, p, x)
bitwise = int(bool(
    (np.asarray(gather(p, x)) == np.asarray(exchange(p, x))).all()))
axis = rules.axis_extent(rules.rules.get("experts"))
print("MOE " + json.dumps({
    "us_gather": us_gather, "us_exchange": us_exchange,
    "bitwise_equal": bitwise, "n_experts": cfg.num_experts,
    "experts_axis": axis,
    "experts_per_device": cfg.num_experts // axis}))
'''


def run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    # A forced host mesh by design: the child stays off any accelerator
    # (which this process may hold).
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _PROBE],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    if out.returncode != 0:
        raise RuntimeError(f"moe dispatch probe failed:\n{out.stderr}")
    for line in out.stdout.splitlines():
        if not line.startswith("MOE "):
            continue
        rec = json.loads(line[len("MOE "):])
        emit("moe_dispatch", rec["us_exchange"],
             f"us_gather={rec['us_gather']:.1f};"
             f"us_exchange={rec['us_exchange']:.1f};"
             f"bitwise_equal={rec['bitwise_equal']};"
             f"n_experts={rec['n_experts']};"
             f"experts_axis={rec['experts_axis']};"
             f"experts_per_device={rec['experts_per_device']}")
