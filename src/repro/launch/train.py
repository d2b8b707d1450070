"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt [--reduced]

On the CPU container this trains reduced/small configs for real (the ~100M
example in examples/train_100m.py); on a TPU fleet the same driver runs the
full configs — the mesh and shardings are the only difference.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs import get as get_arch, ARCHS
from repro.configs.base import reduced as reduce_cfg
from repro.core import facility
from repro.data import pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw, schedule
from repro.parallel import api as par
from repro.runtime.elastic import ElasticTrainer, ElasticConfig
from repro.train import steps as S


def build(cfg, *, mesh=None, lr=3e-4, total_steps=1000, grad_accum=1,
          compress=False, seed=0):
    """Returns (make_state, make_step, state_shardings)."""
    opt_cfg = adamw.AdamWConfig(
        lr=schedule.warmup_cosine(lr, min(100, total_steps // 10 + 1),
                                  total_steps))
    rules = par.default_rules(mesh) if mesh is not None else par.current()

    def make_state():
        with par.use_rules(rules):
            return S.init_train_state(cfg, jax.random.key(seed), opt_cfg,
                                      compress=compress)

    step = S.make_train_step(cfg, opt_cfg, grad_accum=grad_accum,
                             compress=compress)

    state_shardings = None
    if mesh is not None:
        ax = S.train_state_axes(cfg, compress=compress)
        abstract = jax.eval_shape(make_state)
        state_shardings = jax.tree.map(
            lambda a, x: NamedSharding(
                mesh, par.param_spec(a.shape, x, rules) if x else P()),
            abstract, ax,
            is_leaf=lambda t: isinstance(t, tuple) and all(
                isinstance(e, (str, type(None))) for e in t))
        jstep = jax.jit(step, in_shardings=(state_shardings, None),
                        donate_argnums=(0,))
    else:
        jstep = jax.jit(step, donate_argnums=(0,))

    def make_step():
        def run(state, batch):
            # The Pallas kernels have no backward pass yet (no custom_vjp),
            # so the training step traces every contract on the XLA
            # lowering, whatever the platform would pick for inference.
            xla = dataclasses.replace(facility.current(), use_pallas=False)
            with par.use_rules(rules), facility.configure(xla):
                return jstep(state, batch)
        return run

    return make_state, make_step, state_shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="mamba2-130m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)

    make_state, make_step, _ = build(
        cfg, lr=args.lr, total_steps=args.steps,
        grad_accum=args.grad_accum, compress=args.compress)

    def batches(start_step):
        def gen():
            step = start_step
            while True:
                b = pipeline.synthetic_batch(cfg, batch=args.batch,
                                             seq=args.seq, step=step)
                yield step, {k: jnp.asarray(v) for k, v in b.items()}
                step += 1
        return gen()

    trainer = ElasticTrainer(
        make_step=make_step, make_state=make_state, batches=batches,
        checkpointer=Checkpointer(args.ckpt_dir),
        cfg=ElasticConfig(ckpt_every=args.ckpt_every))
    t0 = time.time()
    out = trainer.run(args.steps)
    dt = time.time() - t0
    losses = [m["loss"] for m in out["metrics"]]
    print(f"steps={len(losses)} first_loss={losses[0]:.4f} "
          f"last_loss={losses[-1]:.4f} wall={dt:.1f}s "
          f"restarts={out['restarts']}")


if __name__ == "__main__":
    main()
