"""Smoke run of the facility's serve and train paths on a TPU.

    python chip_smoke.py              # one chip: serve, reference, train
    python chip_smoke.py --chips 4    # the (data=2, model=2) mesh phase only
    python chip_smoke.py --reduced    # the same phases at the reduced config

Drives the entry points a user calls — ``launch.serve.serve_loop`` and
``launch.train.build`` under ``runtime.elastic.ElasticTrainer`` — with
mamba2-130m at its published size (24 layers, d_model 768, vocab 50280)
and random weights from ``SEED``, guards off:

  * serve:     8 slots, 512-token prompts, up to 32 generated tokens, 16
               requests; every request completes, the compiled prefill and
               decode steps hold ``tpu_custom_call`` (the Pallas kernels
               compiled, not XLA), and no guard demotion is recorded;
  * reference: Pallas prefill logits against the same params on the XLA
               lowering, within ``LOGIT_TOL`` of the logit scale, with the
               same argmax at prefill and at the first decode step;
  * train:     8 x 2048 tokens, 10 steps; every loss finite, the last
               below the first.

``--chips 4`` runs only the mesh phase: the Pallas prefill and one decode
step under ``par.default_rules`` on a (data=2, model=2) mesh against the
one-chip result (within ``LOGIT_TOL`` as compiled by default, bitwise
compiled with ``EXACT``), and 5 sharded training steps against one chip
(losses within ``MESH_LOSS_RTOL``).

Each phase prints its own lines.  The last line of standard output is
``{"ok": true, "device": {...}}`` only when every phase passed on a TPU at
the published size.  Off a TPU the script exits non-zero: 2 without
running anything, or, with ``--reduced`` (a CPU rehearsal in Pallas
interpret mode), 3 when every phase passed.  A failed phase exits 1.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.checkpoint.checkpoint import Checkpointer  # noqa: E402
from repro.configs import get as get_arch  # noqa: E402
from repro.configs.base import reduced as reduce_cfg  # noqa: E402
from repro.core import autotune, facility, lowering  # noqa: E402
from repro.data import pipeline  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.parallel import api as par  # noqa: E402
from repro.runtime.elastic import ElasticConfig, ElasticTrainer  # noqa: E402
from repro.train import steps as S  # noqa: E402

ARCH = "mamba2-130m"
SEED = 0
# Activations are bf16 between contracts (8-bit mantissa: 2^-8 relative per
# rounding), and the two lowerings round at the same op boundaries but
# accumulate in different orders, through 24 layers.  So the Pallas logits
# may differ from the XLA logits by a few percent of the logit scale.
LOGIT_TOL = 0.05          # max |pallas - xla| <= LOGIT_TOL * max |xla|
MESH_LOSS_RTOL = 1e-2     # |loss_mesh - loss_one| <= RTOL * |loss_one|
# Bitwise equality across partitionings needs every bf16 rounding where the
# program puts it.  By default the TPU compiler may skip bf16 round trips
# inside a fusion, and the partitioner's collectives move fusion
# boundaries, so one chip and the mesh round in different places.  The
# mesh phase's bitwise comparison compiles both sides with this option.
EXACT = {"xla_allow_excess_precision": False}

FULL = {"serve": dict(batch=8, prompt_len=512, gen_len=32, n_requests=16),
        "train": dict(batch=8, seq=2048, steps=10),
        "mesh": dict(batch=2, prompt_len=512, train_batch=8, seq=2048,
                     steps=5)}
REDUCED = {"serve": dict(batch=2, prompt_len=32, gen_len=6, n_requests=4),
           "train": dict(batch=2, seq=64, steps=6),
           "mesh": dict(batch=2, prompt_len=32, train_batch=4, seq=64,
                        steps=3)}

CKPT_DIR = ROOT / ".smoke_ckpt"
AUTOTUNE_FILE = ROOT / ".autotune.json"


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, **kv):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def kernel_config() -> facility.FacilityConfig:
    """The facility config the serve phase runs under: the platform's own
    on a TPU (compiled Pallas), Pallas in interpret mode elsewhere, so a
    CPU rehearsal walks the same dispatch path."""
    fac = facility.current()
    if jax.default_backend() == "tpu":
        return fac
    return dataclasses.replace(fac, use_pallas=True)


def _prompt(cfg, batch: int, prompt_len: int) -> jnp.ndarray:
    rng = np.random.default_rng(SEED + 1)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                                    dtype=np.int32))


def _decode_cache(cfg, pre, batch: int, prompt_len: int):
    """A batched decode cache holding every slot's prefill state."""
    cache = M.init_cache(cfg, batch=batch, seq_len=prompt_len * 4)
    for s in range(batch):
        one = {k: v[:, s:s + 1] for k, v in pre.items()}
        cache = serve._scatter_prefill(cache, one, s, cfg)
    return cache


def _custom_calls(fn, *args) -> tuple[int, float]:
    """Compile ``fn`` for ``args``; (tpu_custom_call count, seconds)."""
    t0 = time.time()
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call"), time.time() - t0


# ----------------------------------------------------------------------
# Phases
# ----------------------------------------------------------------------

def serve_phase(cfg, params, *, batch, prompt_len, gen_len,
                n_requests) -> dict:
    on_tpu = jax.default_backend() == "tpu"
    with facility.configure(kernel_config()):
        cache = M.init_cache(cfg, batch=batch,
                             seq_len=max(prompt_len * 4, gen_len * 2, 8))
        n_pre, t_pre = _custom_calls(
            S.make_prefill_step(cfg), params,
            {"tokens": jnp.zeros((1, prompt_len), jnp.int32)})
        n_dec, t_dec = _custom_calls(
            S.make_serve_step(cfg), params, cache,
            jnp.zeros((batch, 1), jnp.int32))
        log("serve", compile_s_prefill=f"{t_pre:.1f}",
            compile_s_decode=f"{t_dec:.1f}", tpu_custom_calls_prefill=n_pre,
            tpu_custom_calls_decode=n_dec)
        if on_tpu:
            check(n_pre > 0 and n_dec > 0,
                  "compiled prefill/decode hold no tpu_custom_call: the "
                  "kernels did not compile into the steps")
        lowering.clear_guard_state()
        lowering.DISPATCH_COUNTS.clear()
        lowering.SHAPE_RULE_FALLBACKS.clear()
        out = serve.serve_loop(cfg, params, batch=batch,
                               prompt_len=prompt_len, gen_len=gen_len,
                               n_requests=n_requests, seed=SEED,
                               guards=False)
    by_backend: dict = {}
    for (backend, op_class, _), n in lowering.DISPATCH_COUNTS.items():
        key = f"{backend}/{op_class}"
        by_backend[key] = by_backend.get(key, 0) + n
    log("serve", completed=out["completed"], rejected=out["rejected"],
        failed=out["failed"], steps=out["steps"],
        decode_tokens=out["decode_tokens"],
        prefill_tokens=out["prefill_tokens"],
        wall_s=f"{out['wall_s']:.1f}",
        guard_events=len(lowering.GUARD_EVENTS),
        dispatches=json.dumps(by_backend, sort_keys=True).replace(" ", ""),
        peak_bytes_in_use=_peak_bytes(jax.devices()[0]))
    check(out["completed"] == n_requests and out["rejected"] == 0
          and out["failed"] == 0,
          f"served {out['completed']}/{n_requests} "
          f"(rejected {out['rejected']}, failed {out['failed']})")
    check(not lowering.GUARD_EVENTS,
          f"guard demotions recorded: {lowering.GUARD_EVENTS}")
    # XLA dispatches that a shape rule chose (the reduced rehearsal's SSD
    # chunks, shorter than the kernel's row tile) are the facility's own
    # routing; any other is a fallback.
    fallbacks = collections.Counter(
        {(op_class, ger): n for (backend, op_class, ger), n
         in lowering.DISPATCH_COUNTS.items() if backend == "xla"})
    fallbacks.subtract(lowering.SHAPE_RULE_FALLBACKS)
    check(not any(n > 0 for n in fallbacks.values()),
          f"contracts took the XLA lowering: {by_backend} (shape rule: "
          f"{dict(lowering.SHAPE_RULE_FALLBACKS)})")
    return out


def reference_phase(cfg, params, *, prompt_len) -> dict:
    """Pallas prefill (and first decode step) against the XLA lowering."""
    tokens = _prompt(cfg, 1, prompt_len)
    xla = dataclasses.replace(facility.current(), use_pallas=False)
    got = {}
    for name, fac in (("pallas", kernel_config()), ("xla", xla)):
        with facility.configure(fac):
            logits, pre = jax.jit(S.make_prefill_step(cfg))(
                params, {"tokens": tokens})
            cache = _decode_cache(cfg, pre, 1, prompt_len)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            _, dlogits, _ = jax.jit(S.make_serve_step(cfg))(
                params, cache, first)
        got[name] = (np.asarray(logits, np.float32),
                     np.asarray(dlogits[:, -1], np.float32))
    (pl_pre, pl_dec), (xl_pre, xl_dec) = got["pallas"], got["xla"]
    scale = float(np.abs(xl_pre).max())
    diff = float(np.abs(pl_pre - xl_pre).max())
    ok_pre = int(pl_pre.argmax()) == int(xl_pre.argmax())
    ok_dec = int(pl_dec.argmax()) == int(xl_dec.argmax())
    log("reference", logit_scale=f"{scale:.4f}", max_abs_diff=f"{diff:.5f}",
        tol=f"{LOGIT_TOL * scale:.5f}", argmax_prefill_agrees=ok_pre,
        argmax_decode_agrees=ok_dec,
        decode_max_abs_diff=f"{float(np.abs(pl_dec - xl_dec).max()):.5f}")
    check(np.isfinite(pl_pre).all() and np.isfinite(pl_dec).all(),
          "non-finite Pallas logits")
    check(diff <= LOGIT_TOL * scale,
          f"Pallas prefill logits differ from XLA by {diff} "
          f"> {LOGIT_TOL} x {scale}")
    check(ok_pre and ok_dec, "Pallas and XLA argmax disagree")
    return {"max_abs_diff": diff, "scale": scale}


def _train_losses(cfg, *, batch, seq, steps, mesh=None, lr=1e-3,
                  tag="one") -> list[float]:
    ckpt = CKPT_DIR / tag
    shutil.rmtree(ckpt, ignore_errors=True)
    make_state, make_step, shardings = train.build(
        cfg, mesh=mesh, lr=lr, total_steps=steps, seed=SEED)

    def batches(start):
        for step in range(start, steps):
            b = pipeline.synthetic_batch(cfg, batch=batch, seq=seq,
                                         step=step, seed=SEED)
            yield step, {k: jnp.asarray(v) for k, v in b.items()}

    trainer = ElasticTrainer(
        make_step=make_step, make_state=make_state, batches=batches,
        checkpointer=Checkpointer(str(ckpt)),
        cfg=ElasticConfig(ckpt_every=steps + 1), state_shardings=shardings)
    try:
        out = trainer.run(steps)
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    check(out["restarts"] == 0, f"trainer restarted {out['restarts']}x")
    return [m["loss"] for m in out["metrics"]]


def train_phase(cfg, *, batch, seq, steps) -> list[float]:
    t0 = time.time()
    losses = _train_losses(cfg, batch=batch, seq=seq, steps=steps)
    log("train", steps=len(losses), seconds=f"{time.time() - t0:.1f}",
        first_loss=f"{losses[0]:.4f}", last_loss=f"{losses[-1]:.4f}",
        losses=",".join(f"{x:.4f}" for x in losses),
        peak_bytes_in_use=_peak_bytes(jax.devices()[0]))
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    return losses


def mesh_phase(cfg, *, batch, prompt_len, train_batch, seq,
               steps) -> dict:
    """Pallas prefill + one decode step, and sharded training, on a
    (data=2, model=2) mesh of this process's first four devices, each
    against the same work on one device.

    The forward runs twice.  Compiled as users compile it, the mesh is
    held to ``LOGIT_TOL`` of one device's logits, and each row's mesh
    token to one within that tolerance of one device's best.  Compiled
    with ``EXACT``, it is held to bitwise equality (DESIGN §11)."""
    devs = jax.devices()
    check(len(devs) >= 4, f"the mesh phase needs 4 devices, have {len(devs)}")
    mesh = Mesh(np.asarray(devs[:4]).reshape(2, 2), ("data", "model"))
    params = M.init_params(cfg, jax.random.key(SEED))
    tokens = _prompt(cfg, batch, prompt_len)
    on_one = lambda t: jax.device_put(t, devs[0])  # noqa: E731
    replicated = NamedSharding(mesh, P())
    on_mesh = lambda t: jax.device_put(t, replicated)  # noqa: E731

    def forward(put, first=None, options=None):
        """Prefill logits, the logits of one decode step fed ``first``
        (default: the prefill's argmax), and ``first``."""
        p = put(params)
        with facility.configure(kernel_config()):
            logits, pre = jax.jit(S.make_prefill_step(cfg),
                                  compiler_options=options)(
                p, {"tokens": put(tokens)})
            cache = _decode_cache(cfg, pre, batch, prompt_len)
            if first is None:
                first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            _, dlogits, _ = jax.jit(S.make_serve_step(cfg),
                                    compiler_options=options)(
                p, put(cache), put(first))
        return (np.asarray(logits, np.float32),
                np.asarray(dlogits[:, -1], np.float32), first)

    def compare(options) -> dict:
        one = forward(on_one, options=options)
        with par.use_rules(par.default_rules(mesh)):
            four = forward(on_mesh, one[2], options)
        out = {}
        for name, a, b in (("prefill", one[0], four[0]),
                           ("decode", one[1], four[1])):
            rows = np.arange(len(a))
            out[name] = dict(
                bitwise=bool(np.array_equal(a, b)),
                scale=float(np.abs(a).max()),
                max_abs_diff=float(np.abs(a - b).max()),
                argmax_agrees=int((a.argmax(-1) == b.argmax(-1)).sum()),
                gap_to_best=float((a.max(-1) - a[rows, b.argmax(-1)]).max()))
        return out

    t0 = time.time()
    default, exact = compare(None), compare(EXACT)
    for mode, res in (("default", default), ("exact", exact)):
        log("mesh", mode=mode,
            prefill_bitwise=res["prefill"]["bitwise"],
            decode_bitwise=res["decode"]["bitwise"],
            **{f"{k}_{f}": res[k][f] for k in ("prefill", "decode")
               for f in ("scale", "max_abs_diff", "argmax_agrees",
                         "gap_to_best")})
    log("mesh", rows=batch, forward_seconds=f"{time.time() - t0:.1f}")

    t0 = time.time()
    kw = dict(batch=train_batch, seq=seq, steps=steps)
    l_one = _train_losses(cfg, tag="one", **kw)
    l_mesh = _train_losses(cfg, mesh=mesh, tag="mesh", **kw)
    rel = max(abs(a - b) / abs(a) for a, b in zip(l_one, l_mesh))
    log("mesh", train_seconds=f"{time.time() - t0:.1f}",
        losses_one=",".join(f"{x:.4f}" for x in l_one),
        losses_mesh=",".join(f"{x:.4f}" for x in l_mesh),
        max_rel_diff=f"{rel:.2e}", rtol=MESH_LOSS_RTOL)
    for i, d in enumerate(devs[:4]):
        stats = d.memory_stats() or {}
        log("mesh", device=i, bytes_in_use=stats.get("bytes_in_use"),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"))

    for name, res in default.items():
        tol = LOGIT_TOL * res["scale"]
        check(res["max_abs_diff"] <= tol,
              f"sharded {name} logits differ from one device by "
              f"{res['max_abs_diff']} > {LOGIT_TOL} x {res['scale']}")
        check(res["gap_to_best"] <= tol,
              f"a sharded {name} token is {res['gap_to_best']} below one "
              f"device's best logit (> {tol})")
    check(all(res["bitwise"] for res in exact.values()),
          f"sharded Pallas forward compiled with {EXACT} differs from "
          f"one device")
    check(all(np.isfinite(l_mesh)), f"non-finite sharded loss: {l_mesh}")
    check(rel <= MESH_LOSS_RTOL,
          f"sharded losses differ from one device by {rel:.2e} relative")
    return {"default": default, "exact": exact, "max_rel_loss_diff": rel}


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="run the phases at the reduced config (a CPU "
                         "rehearsal); never reports a result")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    platform, count = dev.platform, len(jax.devices())
    if platform != "tpu" and not args.reduced:
        print(f"chip_smoke: no TPU (platform {platform}); nothing run",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    # Block plans come from the in-checkout winner store only (absent: the
    # tiling heuristic), never from a cache elsewhere on the machine.
    os.environ[autotune.DEFAULT_CACHE_ENV] = str(AUTOTUNE_FILE)
    log("device", platform=platform, kind=repr(dev.device_kind),
        count=count, jax=jax.__version__, compile_cache=cache_dir)

    cfg = get_arch(ARCH)
    sizes = FULL
    if args.reduced:
        cfg, sizes = reduce_cfg(cfg), REDUCED
    log("config", arch=cfg.name, layers=cfg.num_layers,
        d_model=cfg.d_model, vocab=cfg.vocab_size, reduced=args.reduced)
    try:
        if args.chips == 4:
            mesh_phase(cfg, **sizes["mesh"])
        else:
            params = M.init_params(cfg, jax.random.key(SEED))
            serve_phase(cfg, params, **sizes["serve"])
            reference_phase(cfg, params,
                            prompt_len=sizes["serve"]["prompt_len"])
            del params
            train_phase(cfg, **sizes["train"])
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if platform != "tpu" or args.reduced:
        print(f"chip_smoke: every phase passed at "
              f"{'the reduced' if args.reduced else 'the full'} config on "
              f"{platform}; no result is reported off a TPU at full size",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
