"""chip_smoke.py off the chip: the reduced phases pass in-process, the
script never reports success off a TPU, and the platform-following pieces
it relies on (interpret resolution, the compile-cache helper, the
head-major flash kernel, the kernel-safe erf) hold on the CPU."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get as get_arch
from repro.configs.base import reduced
from repro.core import facility, lowering
from repro.core.facility import Plan
from repro.core.precision import Ger
from repro.kernels import epilogue as E
from repro.kernels import mma_attention as KA
from repro.launch import compile_cache
from repro.models import model as M

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reduced_phases_pass_in_process(smoke):
    cfg = reduced(get_arch(smoke.ARCH))
    sizes = smoke.REDUCED
    params = M.init_params(cfg, jax.random.key(0))
    out = smoke.serve_phase(cfg, params, **sizes["serve"])
    assert out["completed"] == sizes["serve"]["n_requests"]
    ref = smoke.reference_phase(
        cfg, params, prompt_len=sizes["serve"]["prompt_len"])
    assert ref["max_abs_diff"] <= smoke.LOGIT_TOL * ref["scale"]
    losses = smoke.train_phase(cfg, **sizes["train"])
    assert losses[-1] < losses[0]
    assert not smoke.CKPT_DIR.exists()


def test_no_result_off_a_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    flags = os.environ.get("XLA_FLAGS")
    assert smoke.main([]) == 2
    assert smoke.main(["--chips", "4"]) == 2
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert os.environ.get("XLA_FLAGS") == flags


def test_mesh_phase_rehearsal_on_forced_host_devices(tmp_path):
    """The --chips 4 phase on four forced CPU devices: the sharded Pallas
    forward compiled with ``EXACT`` equals one device's bitwise, and
    sharded training tracks it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"      # a forced host mesh, never a chip
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--reduced",
         "--chips", "4"], capture_output=True, text=True, env=env,
        timeout=600)
    assert out.returncode == 3, out.stdout + out.stderr
    assert ("mode=exact prefill_bitwise=True decode_bitwise=True"
            in out.stdout)
    last = out.stdout.strip().splitlines()[-1]
    assert '"ok"' not in last and not last.startswith("{")


def test_interpret_and_backend_follow_the_cpu_platform(rng):
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(32, 128)), jnp.float32)
    cfg = facility.FacilityConfig()
    assert cfg.use_pallas is None and cfg.interpret is None
    lowering.DISPATCH_COUNTS.clear()
    with facility.configure(cfg):
        want = facility.contract("mk,kn->mn", x, y,
                                 plan=Plan(ger=Ger.F32GER))
        # compiled Pallas cannot run on the CPU: this only passes when
        # interpret resolved to True
        got = facility.contract("mk,kn->mn", x, y,
                                plan=Plan(ger=Ger.F32GER, backend="pallas"))
    assert lowering.DISPATCH_COUNTS[("xla", "gemm", "xvf32ger")] == 1
    assert lowering.DISPATCH_COUNTS[("pallas", "gemm", "xvf32ger")] == 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_compile_cache_helper(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    # the cache is keyed on the programs' metadata (their op names) in
    # either case; a directory is set only where the environment names none
    keyed = ("jax_compilation_cache_include_metadata_in_key", True)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == [keyed]

    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert calls == [keyed, ("jax_compilation_cache_dir", first)] * 2


@pytest.mark.parametrize("b,sq,sk,h,kvh,d,causal,window,valid", [
    (1, 64, 64, 8, 2, 128, True, None, False),      # causal GQA
    (1, 128, 128, 4, 4, 120, True, 48, False),      # sliding window
    (4, 16, 256, 4, 2, 64, False, None, True),      # valid slots, B > 1
], ids=["causal-gqa", "window", "valid-batched"])
def test_head_major_flash_kernel_interpret(b, sq, sk, h, kvh, d, causal,
                                           window, valid, rng):
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, kvh, d)), jnp.float32)
    mask = jnp.asarray(rng.random((b, sk)) > 0.3) if valid else None
    got = KA.mma_flash_attention(q, k, v, causal=causal, window=window,
                                 valid=mask, block_q=32, block_k=128,
                                 interpret=True)
    want = KA.ref_attention(q, k, v, causal=causal, window=window,
                            valid=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_erf_matches_lax_erf():
    x = np.concatenate([np.linspace(-6, 6, 200_001, dtype=np.float32),
                        np.float32([0.0, -0.0, np.inf, -np.inf])])
    got = np.asarray(jax.jit(E.erf)(x))
    want = np.asarray(jax.jit(jax.lax.erf)(x))
    np.testing.assert_array_equal(got, want)
    # eager evaluation fuses differently: within f32 rounding
    eager = np.asarray(E.erf(jnp.asarray(x)))
    np.testing.assert_allclose(eager, want, rtol=0, atol=4e-7)
    # the fused gelu epilogue against the lax.erf formulation
    v = jnp.asarray(np.linspace(-8, 8, 4097, dtype=np.float32))
    gelu = np.asarray(jax.jit(E.ACTIVATIONS["gelu"])(v))
    ref = np.asarray(jax.jit(lambda t: jax.nn.gelu(t, approximate=False))(v))
    np.testing.assert_allclose(gelu, ref, rtol=1e-6, atol=1e-6)


def test_last_line_contract_is_the_json_device_record(smoke, capsys,
                                                      monkeypatch):
    """On a TPU every phase passing prints exactly the device record last;
    here the platform and phases are stood in for."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {}

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    monkeypatch.setenv(smoke.autotune.DEFAULT_CACHE_ENV, "unset")
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "cache")
    for name in ("serve_phase", "reference_phase", "train_phase"):
        monkeypatch.setattr(smoke, name, lambda *a, **k: None)
    monkeypatch.setattr(smoke.M, "init_params", lambda *a, **k: None)
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
