"""The one general generator: it drives the program through a job whose
parameters a traffic file gives.

Three kinds of job, named by the traffic file's ``job``:

``generate``  static batches of ``batch`` prompts of ``prompt_len`` tokens,
              each answered with ``gen_len`` greedy tokens: one batched
              prefill, the prefill state handed to the decode cache, then
              ``gen_len - 1`` decode steps.  Batches repeat until the
              window closes.
``score``     one ``prompt_len``-token prompt per request, batch 1, its
              answer the next-token logits; ``in_flight`` requests are
              enqueued before the client waits for the oldest answer.
``train``     steps of ``batch`` x ``seq`` tokens through the trainer.

Every job makes its weights on the device from the seed, in one jitted
call, and its inputs from the seed.  A configuration with a ``mesh``
(a score job) makes each chip's shard of the weights, as the program lays
them out, and runs the program across the mesh under its own sharding
rules.  ``setup`` compiles and warms up; ``window`` measures; ``check``
compares what the window produced with the configuration's plain
reference after the program's state is freed.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchlib import compare, counts, spec, trace
from references import common

from repro.configs.base import ArchConfig, reduced as reduce_arch
from repro.core import facility
from repro.launch import compile_cache, serve, train
from repro.launch import specs as SP
from repro.models import model as M
from repro.optim import adamw
from repro.parallel import api as par
from repro.runtime.elastic import ElasticConfig, ElasticTrainer
from repro.train import steps as S


# ------------------------------------------------------------ the model

@dataclasses.dataclass
class Model:
    cfg: dict           # the configuration as run (reference side)
    arch: ArchConfig    # the same, as the program takes it
    ref: object         # the plain reference module
    rules: par.ShardingRules | None = None   # the mesh's, where one is

    @property
    def param_shardings(self):
        """Each weight's ``NamedSharding`` on the mesh: the program's own
        ``param_spec`` over ``param_axes``, as its launchers lay them."""
        abstract = jax.eval_shape(lambda: M.init_params(self.arch,
                                                        jax.random.key(0)))
        tree = par.tree_param_specs(abstract, M.param_axes(self.arch),
                                    self.rules)
        return jax.tree.map(lambda p: NamedSharding(self.rules.mesh, p),
                            tree, is_leaf=lambda x: isinstance(x, P))

    def jit(self, step, batch: dict):
        """``jax.jit`` of a ``step(params, batch)``; on a mesh, with the
        weights' shardings and ``batch`` (arrays of the shapes it will
        be given) laid out by the program's rules."""
        if self.rules is None:
            return jax.jit(step)
        axes = SP.batch_axes(self.arch, for_train=False)
        return jax.jit(step, in_shardings=(self.param_shardings, {
            k: NamedSharding(self.rules.mesh, par.activation_spec(
                v.shape, axes[k], self.rules)) for k, v in batch.items()}))

    @contextlib.contextmanager
    def running(self):
        """The program's facility config and, on a mesh, its sharding
        rules and the mesh: every contraction traced here is placed by
        the program's own shard planner."""
        with facility.configure(kernel_config()):
            if self.rules is None:
                yield
            else:
                with par.use_rules(self.rules), self.rules.mesh:
                    yield


def make_mesh(config: dict, devs) -> par.ShardingRules | None:
    """The program's default rules over a mesh of ``devs`` shaped as the
    configuration's ``mesh`` (axis name -> size, in order); ``None`` for
    a configuration without one, or without devices (its sizes only)."""
    shape = config.get("mesh")
    if not shape or devs is None:
        return None
    grid = mesh_utils.create_device_mesh(tuple(shape.values()),
                                         devices=list(devs))
    return par.default_rules(Mesh(grid, tuple(shape)))


def make_model(config: dict, rehearse: bool, devs=None) -> Model:
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    arch = ArchConfig(**{k: v for k, v in config.items() if k in names})
    if rehearse:
        arch = reduce_arch(arch)
    return Model(cfg=dataclasses.asdict(arch), arch=arch,
                 ref=spec.reference(config["reference"]),
                 rules=make_mesh(config, devs))


def make_job(cell, seed: int, rehearse: bool = False, devs=None):
    """The job of a cell (``spec.Cell``) on the devices ``devs``: its
    traffic file's parameters, with its ``rehearse`` block over them in a
    rehearsal."""
    p = dict(cell.traffic, **(cell.traffic.get("rehearse", {}) if rehearse
                              else {}))
    model = make_model(cell.config, rehearse, devs)
    if model.rules is not None and p["job"] != "score":
        raise ValueError(f"{cell.name}: a mesh runs score jobs only")
    return JOBS[p["job"]](model, p, seed)


def kernel_config() -> facility.FacilityConfig:
    """The platform's own facility config (compiled Pallas on a TPU); off
    a TPU, Pallas in interpret mode, so a rehearsal walks the same
    dispatch path."""
    fac = facility.current()
    if jax.default_backend() == "tpu":
        return fac
    return dataclasses.replace(fac, use_pallas=True)


def make_weights(model: Model, seed: int):
    """The weights, made on the device from the seed in one jitted call,
    in the program's layout, which is checked against its own init.  On a
    mesh each chip makes only its own shard of each weight."""
    placed = ({} if model.rules is None else
              {"out_shardings": model.param_shardings})
    w = jax.jit(lambda k: model.ref.init_weights(model.cfg, k), **placed)(
        common.key_from_seed(seed))
    want = jax.eval_shape(lambda: M.init_params(model.arch,
                                                jax.random.key(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), w)
    if got != jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise ValueError("the reference's weight layout is not the "
                         "program's: " + str(jax.tree.structure(want)))
    return jax.block_until_ready(w)


def tokens(rng: np.random.Generator, shape, vocab: int, dist: dict):
    """Token ids: ``uniform`` over the vocabulary, or ``zipf`` with
    exponent ``a``, folded into the vocabulary."""
    if dist["kind"] == "uniform":
        return rng.integers(0, vocab, shape, dtype=np.int64).astype(np.int32)
    return (rng.zipf(dist["a"], size=shape) % vocab).astype(np.int32)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of the seed; a stream's numbers may be
    -1 (the warm-up's inputs), never lower."""
    return np.random.default_rng([int(seed), *(s + 1 for s in stream)])


def window_opens() -> tuple[float, collections.Counter]:
    """The measured window's opening: the clock, and the program's compile
    counters, which the harness reads again when the window closes."""
    counts_now = collections.Counter(compile_cache.COMPILE_COUNTS)
    return time.perf_counter(), counts_now


def _sample(seed: int, population: int, k: int) -> list[int]:
    rng = rng_for(seed, 7)
    return sorted(rng.choice(population, size=min(k, population),
                             replace=False).tolist())


# ---------------------------------------------------------- generation

class Generate:
    kind = "generate"

    def __init__(self, model: Model, p: dict, seed: int):
        self.model, self.seed = model, seed
        self.batch, self.prompt_len = p["batch"], p["prompt_len"]
        self.gen_len, self.check_rows = p["gen_len"], p["check_rows"]
        self.dist, self.trace_units = p["tokens"], p["trace_units"]
        self.served: list[np.ndarray] = []

    def _prompts(self, unit: int) -> np.ndarray:
        return tokens(rng_for(self.seed, 1, unit),
                      (self.batch, self.prompt_len),
                      self.model.cfg["vocab_size"], self.dist)

    def setup(self):
        arch, b = self.model.arch, self.batch
        self.weights = make_weights(self.model, self.seed)

        def handoff(logits, pre):
            cache = M.init_cache(arch, batch=b,
                                 seq_len=self.prompt_len + self.gen_len)
            for s in range(b):
                one = {k: v[:, s:s + 1] for k, v in pre.items()}
                cache = serve._scatter_prefill(cache, one, s, arch)
            return cache, jnp.argmax(logits, -1).astype(jnp.int32)[:, None]

        self.prefill = jax.jit(S.make_prefill_step(arch))
        self.handoff = jax.jit(handoff)
        self.decode = jax.jit(S.make_serve_step(arch), donate_argnums=(1,))
        with facility.configure(kernel_config()):
            self._batch(self._prompts(-1), steps=2)

    def _batch(self, prompts, steps, tracer=None):
        span = (tracer or trace.Tracer(0, None)).span
        w = self.weights
        with span("prefill"):
            logits, pre = self.prefill(w, {"tokens": jnp.asarray(prompts)})
        with span("handoff"):
            cache, tok = self.handoff(logits, pre)
            del logits, pre
        out = [tok]
        with span("decode"):
            for _ in range(steps):
                tok, _, cache = self.decode(w, cache, tok)
                out.append(tok)
        with span("client"):
            got = np.concatenate(jax.device_get(out), axis=1)
        return got

    def window(self, seconds: float, tracer) -> dict:
        done = 0
        with facility.configure(kernel_config()):
            t0, at_open = window_opens()
            t_end = t0
            ends = [t0]
            while t_end - t0 < seconds:
                tracer.before(done)
                self.served.append(self._batch(self._prompts(done),
                                               self.gen_len - 1, tracer))
                t_end = time.perf_counter()
                ends.append(t_end)
                tracer.after(done)
                done += 1
        rows = done * self.batch
        return {"attempted": rows, "failed": 0, "opened": t0, "ends": ends,
                "compiles_at_open": at_open,
                "end_to_end": {"gen_tok_s": rows * self.gen_len
                               / (t_end - t0)},
                "unit_work": counts.generate_batch(
                    self.model.cfg, self.batch, self.prompt_len,
                    self.gen_len)}

    def free(self):
        for name in ("prefill", "handoff", "decode"):
            setattr(self, name, None)

    def _picks(self) -> list[tuple[int, int]]:
        """``check_rows`` (batch, row) pairs drawn from the seed: rows
        evenly spaced over the batch from a drawn offset, so that every
        part of a batch is sampled, each from a drawn finished batch."""
        rng = rng_for(self.seed, 7)
        offset = int(rng.integers(self.batch))
        step = self.batch / self.check_rows
        return [(int(rng.integers(len(self.served))),
                 (offset + int(j * step)) % self.batch)
                for j in range(self.check_rows)]

    def check(self, control: bool = False) -> dict:
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over ``check_rows`` sequences drawn
        from the seed, each read at every generated position."""
        toks, served = [], []
        for unit, row in self._picks():
            toks.append(np.concatenate([self._prompts(unit)[row],
                                        self.served[unit][row, :-1]]))
            served.append(self.served[unit][row])
        toks, served = jnp.asarray(np.stack(toks)), jnp.asarray(
            np.stack(served))
        start, ref = self.prompt_len - 1, self.model.ref

        def logits(w, t, cfg, mm):
            return ref.logits(w, t, cfg, mm)[:, start:]
        return compare.served_gaps(logits, self.weights, toks, served,
                                   self.model.cfg, "gen_gap", control)


# ------------------------------------------------------------- scoring

class Score:
    kind = "score"

    def __init__(self, model: Model, p: dict, seed: int):
        self.model, self.seed = model, seed
        self.prompt_len, self.in_flight = p["prompt_len"], p["in_flight"]
        self.check_prompts, self.dist = p["check_prompts"], p["tokens"]
        self.trace_units = p["trace_units"]
        self.answers: list[np.ndarray] = []

    def _prompt(self, i: int) -> np.ndarray:
        return tokens(rng_for(self.seed, 2, i), (1, self.prompt_len),
                      self.model.cfg["vocab_size"], self.dist)

    def setup(self):
        self.weights = make_weights(self.model, self.seed)
        warm = {"tokens": jnp.asarray(self._prompt(-1))}
        self.prefill = self.model.jit(S.make_prefill_step(self.model.arch),
                                      warm)
        with self.model.running():
            logits, _ = self.prefill(self.weights, warm)
            np.asarray(logits)[0]

    def window(self, seconds: float, tracer) -> dict:
        pending = collections.deque()
        sent = 0
        with self.model.running():
            t0, at_open = window_opens()
            t_end = t0
            ends = [t0]
            while True:
                open_ = t_end - t0 < seconds
                if open_ and len(pending) < self.in_flight:
                    tracer.before(sent)
                    with tracer.span("prefill"):
                        logits, _ = self.prefill(
                            self.weights,
                            {"tokens": jnp.asarray(self._prompt(sent))})
                    pending.append((sent, logits))
                    sent += 1
                    continue
                if not pending:
                    break
                i, logits = pending.popleft()
                with tracer.span("client"):
                    self.answers.append(np.asarray(logits)[0])
                t_end = time.perf_counter()
                ends.append(t_end)
                tracer.after(i)
        n = len(self.answers)
        return {"attempted": sent, "failed": sent - n, "opened": t0,
                "ends": ends, "compiles_at_open": at_open,
                "end_to_end": {"prompt_tok_s": n * self.prompt_len
                               / (t_end - t0)},
                "unit_work": counts.score_prompt(self.model.cfg,
                                                 self.prompt_len)}

    def free(self):
        self.prefill = None

    def check(self, control: bool = False) -> dict:
        """The largest logit error of an answer over the reference's
        largest logit, widest over ``check_prompts`` answers drawn from the
        seed."""
        picks = _sample(self.seed, len(self.answers), self.check_prompts)
        cfg, last = self.model.cfg, self.model.ref.last_logits
        ref = jax.jit(lambda w, t, mm_name: last(
            w, t, cfg, common.PRECISIONS[mm_name]), static_argnums=2)
        out = {"score_err": 0.0}
        if control:
            out["control"] = {"score_err": 0.0}
        for i in picks:
            t = jnp.asarray(self._prompt(i))
            r = np.asarray(ref(self.weights, t, "highest")[0])
            out["score_err"] = max(out["score_err"], compare.logit_error(
                r, self.answers[i]))
            if control:
                low = np.asarray(ref(self.weights, t, "fp8")[0])
                out["control"]["score_err"] = max(
                    out["control"]["score_err"], compare.logit_error(r, low))
        return out


# ------------------------------------------------------------ training

class HeldCheckpoint:
    """The trainer's checkpointer for a measured run: it keeps a reference
    to the state it is handed and writes nothing, so a run's disk writes
    stay small.  Periodic saves are a cell of their own (PERF.md)."""

    def __init__(self):
        self.state = None

    def latest_step(self):
        return None

    def restore(self, step, state, shardings=None):
        raise RuntimeError("a measured run never restores")

    def save_async(self, step, state):
        self.state = state

    save = save_async

    def wait(self):
        pass


def _leaf_norms(tree) -> jnp.ndarray:
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in jax.tree.leaves(tree)])


class Train:
    kind = "train"

    def __init__(self, model: Model, p: dict, seed: int):
        self.model, self.seed = model, seed
        self.batch, self.seq, self.dist = p["batch"], p["seq"], p["tokens"]
        self.opt = p["optimizer"]
        self.warm_steps, self.check_steps = p["warm_steps"], p["check_steps"]
        self.feed_steps = p["feed_steps"]
        self.check_rows, self.trace_units = p["check_rows"], p["trace_units"]
        self.losses: list[float] = []
        self.times: list[float] = []
        self.readings: dict = {}

    def _batch(self, step: int) -> dict:
        t = tokens(rng_for(self.seed, 3, step), (self.batch, self.seq + 1),
                   self.model.cfg["vocab_size"], self.dist)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    def setup(self):
        """Builds the one trainer object.  Its ``run`` starts in
        ``window``: the first ``warm_steps`` steps compile and record what
        ``check`` compares, are set-up, and the same run goes on into the
        measured steps."""
        o = self.opt
        _, make_step, _ = train.build(self.model.arch, lr=o["lr"],
                                      total_steps=o["total_steps"],
                                      seed=0)
        b1 = o["b1"]

        def make_state():
            w = make_weights(self.model, self.seed)
            return {"params": w, "opt": adamw.init_state(w)}

        norms = jax.jit(_leaf_norms)
        diff_norms = jax.jit(lambda a, b: _leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))

        def wrapped():
            step_fn = make_step()
            count = [0]
            first = {}

            def run(state, batch):
                k = count[0]
                if k == 0:
                    first["params"] = jax.tree.map(jnp.copy,
                                                   state["params"])
                with self.tracer.span("train_step"):
                    state, metrics = step_fn(state, batch)
                if k == 0:
                    self.readings["grad"] = np.asarray(
                        norms(state["opt"]["m"])) / (1 - b1)
                if k == self.check_steps - 1:
                    self.readings["update"] = np.asarray(
                        diff_norms(state["params"], first.pop("params")))
                count[0] += 1
                return state, metrics
            return run

        self.tracer = trace.Tracer(0, None)
        self.trainer = ElasticTrainer(
            make_step=wrapped, make_state=make_state,
            batches=self._batches, checkpointer=HeldCheckpoint(),
            cfg=ElasticConfig(ckpt_every=o["total_steps"] + 1),
            on_step=self._on_step)

    def _batches(self, start):
        """The trainer's feed.  The window's first ``feed_steps`` batches
        are made and put on the device during set-up, so that no host work
        of the generator's lies between two measured steps."""
        ahead = {}
        step = start
        while True:
            unit = step - self.warm_steps
            if unit == 0:
                ahead = {s: self._device_batch(s) for s in
                         range(step, step + self.feed_steps)}
                jax.block_until_ready(ahead)
                self.opened, self.at_open = window_opens()
                self.deadline = self.opened + self.seconds
            if unit >= 0:
                if time.perf_counter() >= self.deadline:
                    return
                self.tracer.before(unit)
            with self.tracer.span("batch"):
                b = ahead.pop(step, None) or self._device_batch(step)
            yield step, b
            step += 1

    def _device_batch(self, step: int) -> dict:
        return {k: jnp.asarray(v) for k, v in self._batch(step).items()}

    def _on_step(self, step, loss, dt):
        self.losses.append(loss)
        self.times.append(time.perf_counter())
        if step >= self.warm_steps:
            # the trainer has waited for the step's loss: mark the step's
            # end on the trace's clock, where the traced window closes
            with self.tracer.span("step_done"):
                pass
            self.tracer.after(step - self.warm_steps)

    def window(self, seconds: float, tracer) -> dict:
        self.seconds, self.tracer = seconds, tracer
        out = self.trainer.run(self.opt["total_steps"])
        self.tracer = trace.Tracer(0, None)
        self.final_state = out["state"]
        t = [self.opened] + self.times[self.warm_steps:]
        steps = len(t) - 1
        return {"attempted": steps, "failed": 0, "opened": self.opened,
                "ends": t, "compiles_at_open": self.at_open,
                "end_to_end": {"train_tok_s": steps * self.batch * self.seq
                               / (t[-1] - t[0])},
                "unit_work": (counts.train_step(self.model.cfg, self.batch,
                                                self.seq), counts.Work())}

    def free(self):
        self.final_state = None
        self.trainer = None

    def check(self, control: bool = False) -> dict:
        prog = {"loss": np.asarray(self.losses[:self.check_steps]),
                "grad": self.readings["grad"],
                "update": self.readings["update"]}
        ref = self._reference("highest")
        out = compare.train_numbers(prog, ref)
        if control:
            out["control"] = compare.train_numbers(self._reference("fp8"),
                                                   ref)
        return out

    def _reference(self, mm_name: str) -> dict:
        """The reference's first ``check_steps`` steps from the same
        weights and batches: losses, the clipped first gradient's leaf
        norms, and the leaf norms of the weights' change."""
        cfg, o, ref = self.model.cfg, self.opt, self.model.ref
        mm = common.PRECISIONS[mm_name]
        w0 = jax.jit(lambda k: ref.init_weights(cfg, k))(
            common.key_from_seed(self.seed))
        grad_block = jax.jit(jax.value_and_grad(
            lambda w, t, l: ref.nll_sum(w, t, l, cfg, mm)))
        n_tok = self.batch * self.seq
        w, m, v = w0, None, None
        out = {"loss": []}
        for step in range(self.check_steps):
            b = self._batch(step)
            loss, grads = 0.0, None
            for r in range(0, self.batch, self.check_rows):
                val, g = grad_block(w, jnp.asarray(b["tokens"][r:r + self.check_rows]),
                                    jnp.asarray(b["labels"][r:r + self.check_rows]))
                loss += float(val)
                grads = g if grads is None else jax.tree.map(jnp.add,
                                                             grads, g)
            grads = jax.tree.map(lambda g: g / n_tok, grads)
            out["loss"].append(loss / n_tok)
            w, m, v, clipped = compare.adamw_step(w, grads, m, v, step + 1,
                                                  o)
            if step == 0:
                out["grad"] = np.asarray(_leaf_norms(clipped))
        out["update"] = np.asarray(_leaf_norms(
            jax.tree.map(jnp.subtract, w, w0)))
        out["loss"] = np.asarray(out["loss"])
        return out


JOBS = {c.kind: c for c in (Generate, Score, Train)}
