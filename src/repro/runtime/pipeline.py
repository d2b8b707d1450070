"""Pipeline parallelism: GPipe-style stage executor over shard_map.

For cross-pod scaling beyond the 2-D (data, model) production mesh, layers
are divided into S contiguous stages laid out on a 'stage' mesh axis; a
microbatch stream flows through the stages with `jax.lax.ppermute`
neighbor transfers.  The steady-state bubble is (S-1)/(S-1+M) for M
microbatches; the collective pattern (point-to-point ring shifts, no
all-to-all) is what crosses the slow inter-pod links.

Implementation: every device holds its stage's parameters (stacked layer
pytree sharded on the leading axis over 'stage').  One `shard_map` program
runs M + S - 1 "ticks"; on each tick a device runs its stage on the
current activation and ppermutes the result to the next stage.  This is
the standard single-program GPipe schedule (MaxText/praxis-style) —
deterministic, jit-compatible, and composable with DP inside each stage.

Stage bodies and the mesh-native contract (DESIGN.md section 11): a
``stage_fn`` executes INSIDE the shard_map trace, so every contract it
issues must bind ``Plan(mesh=False)`` — the activation it sees is already
this stage's shard, and a nested sharded dispatch would try to shard_map
a tracer.  The ring itself is a sanctioned collective surface (analysis
rule ``collective-purity``): raw ppermute/shard_map live here so stage
bodies never touch a collective primitive — they only call
``facility.contract``.  Each ring launch consults the facility-wide
``collective`` fault point (runtime/faults.py) like every other comm edge
of the sharded lowering path.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import facility
from repro.runtime import faults as _faults


def pipeline_apply(stage_fn: Callable, params, x, *, mesh: Mesh,
                   axis: str = "stage", microbatches: int | None = None,
                   on_chunk: Callable | None = None,
                   chunk: int | None = None):
    """Run x through all pipeline stages.

    stage_fn(stage_params, h) -> h : one stage's computation (same shape).
    Contracts inside ``stage_fn`` must bind ``Plan(mesh=False)`` (the
    body runs per-shard inside this function's shard_map).
    params: pytree with leading axis = n_stages (sharded over `axis`).
    x: (batch, ...) global input; batch must divide into microbatches.

    ``on_chunk(done_microbatches, total_microbatches)`` turns on chunked
    launch: the microbatch stream is split into ``chunk``-sized pipeline
    fills (default one fill, i.e. ``n_stages`` microbatches) that launch
    back-to-back, with the callback fired on the host between chunks —
    live progress for long streams at the cost of one extra pipeline
    bubble per chunk.  Leave it None for the single fused launch.
    """
    n_stages = mesh.shape[axis]
    mb = microbatches or n_stages
    assert x.shape[0] % mb == 0, (x.shape, mb)

    def run(xin, n_mb):
        """One fused GPipe launch over ``n_mb`` microbatches."""
        _faults.maybe_inject(_faults.COLLECTIVE)

        def per_device(pp, xs):
            stage = jax.lax.axis_index(axis)
            sp = jax.tree.map(lambda a: a[0], pp)
            xs = xs.reshape(n_mb, -1, *xs.shape[1:])    # (M, b/M, ...)
            buf = jnp.zeros_like(xs[0])
            outs = jnp.zeros_like(xs)
            n_ticks = n_mb + n_stages - 1

            def tick(t, carry):
                buf, outs = carry
                # stage 0 ingests microbatch t (when available)
                mb_idx = jnp.clip(t, 0, n_mb - 1)
                inject = jnp.where(t < n_mb, xs[mb_idx],
                                   jnp.zeros_like(buf))
                cur = jnp.where(stage == 0, inject, buf)
                cur = stage_fn(sp, cur)
                # last stage emits microbatch t - (S-1)
                out_idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
                emit = (stage == n_stages - 1) & (t >= n_stages - 1)
                outs = jax.lax.cond(
                    emit,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, cur, out_idx, 0),
                    lambda o: o, outs)
                # shift to next stage (ring; wraparound value is ignored)
                buf = jax.lax.ppermute(
                    cur, axis,
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
                return buf, outs

            buf, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
            # only the last stage's outs are real; broadcast via psum
            outs = jnp.where(stage == n_stages - 1, outs,
                             jnp.zeros_like(outs))
            outs = jax.lax.psum(outs, axis)
            return outs.reshape(-1, *outs.shape[2:])

        pspec_params = jax.tree.map(lambda _: P(axis), params)
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(pspec_params, P()), out_specs=P(),
            check_vma=False)(params, xin)

    if on_chunk is None:
        return run(x, mb)

    # Chunked launch: C-microbatch fills back-to-back, host callback in
    # between.  Same schedule per fill, so the concatenated output equals
    # the fused launch's (tests/test_parallel.py).
    c = chunk or n_stages
    c = min(c, mb)
    while mb % c:
        c -= 1
    per = x.shape[0] // mb
    outs = []
    for i in range(mb // c):
        outs.append(run(x[i * c * per:(i + 1) * c * per], c))
        outs[-1].block_until_ready()
        on_chunk((i + 1) * c, mb)
    return jnp.concatenate(outs, axis=0)


def make_pipelined_mlp(key, n_stages: int, d: int, d_ff: int,
                       backend: str = "xla"):
    """Demo model for tests/examples: n_stages of [Linear, gelu, Linear].

    Every stage matmul dispatches through ``facility.contract`` with
    ``mesh=False`` (the stage body is already inside the pipeline's
    shard_map) — the pipeline composes with the guarded ladder and, when
    ``backend="pallas"``, with the facility's kernels per stage.
    """
    ks = jax.random.split(key, n_stages)

    def init_one(k):
        k1, k2 = jax.random.split(k)
        return {"w1": jax.random.normal(k1, (d, d_ff), jnp.float32)
                * (d ** -0.5),
                "w2": jax.random.normal(k2, (d_ff, d), jnp.float32)
                * (d_ff ** -0.5)}

    params = jax.vmap(init_one)(ks)

    def stage_fn(sp, h):
        # Facility-routed (was raw `@`): F32GER + the xla backend is the
        # same f32 dot_general with an f32 accumulator, and the per-stage
        # dot stays a plain shardable dot_general under shard_map.
        mm = functools.partial(
            facility.contract, facility.DOT,
            plan=facility.Plan(ger=facility.Ger.F32GER, backend=backend,
                               out_dtype=jnp.float32, mesh=False))
        return h + mm(jax.nn.gelu(mm(h, sp["w1"])), sp["w2"])

    def ref_apply(params, x):
        def body(h, sp):
            return stage_fn(sp, h), None
        out, _ = jax.lax.scan(body, x, params)
        return out

    return params, stage_fn, ref_apply
