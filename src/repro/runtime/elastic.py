"""Elastic / fault-tolerant training driver.

Large fleets fail constantly; the framework's contract (DESIGN.md section 5):

  * **Checkpoint/restart**: async sharded checkpoints every
    ``ckpt_every`` steps; on any failure the driver restores the latest
    complete step.  Because the data pipeline is step-addressable
    (repro.data.pipeline), restart resumes the exact batch sequence.
  * **Elastic rescale**: the checkpoint stores *global* arrays, so a
    restart may build a *different* mesh (fewer/more healthy hosts);
    restore re-slices onto the new mesh's shardings.  ``ElasticTrainer``
    takes a ``mesh_factory`` it re-invokes after every failure.
  * **Straggler mitigation**: a per-step wall-clock watchdog.  Steps
    slower than ``straggler_factor`` x the trailing median are counted;
    after ``straggler_patience`` consecutive slow steps the driver raises
    ``StragglerDetected`` so the launcher can swap the slow host (on this
    container we surface the signal and keep going — the policy hook is
    the deliverable).  On real fleets this watchdog pairs with hot
    spares; the trigger logic is identical.
  * **Failure injection**: the facility-wide registry
    (``repro.runtime.faults``) owns injection — pass a
    :class:`~repro.runtime.faults.FaultPlan` as ``faults=``, or use the
    legacy ``cfg.fail_at_steps`` shorthand, which the trainer translates
    into ``train.step`` at-step specs on the same plan ("a node dies
    once" is the registry's at-step semantics).  ``raise`` kinds become
    :class:`SimulatedFailure` (the restart path), ``latency`` kinds
    become injected stragglers the watchdog must catch.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, Iterable

import jax

from repro.checkpoint.checkpoint import Checkpointer
from repro.runtime import faults as _faults


# The trainer loop's host spans, in the profiler's trace when one runs: a
# step (``repro.trainer.step``, the profiler's step marker) holds the feed's
# ``batch``, the step function's ``dispatch``, the ``wait`` for its loss, the
# ``log`` of that loss (``on_step`` included) and, every ``ckpt_every``
# steps, the ``checkpoint`` call.  The end-of-run save is a ``checkpoint``
# span of its own.  The last step span holds only the ``batch`` fetch that
# found the feed's end.
SPAN_PREFIX = "repro.trainer."


def _span(name: str):
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def _span_step(step: int):
    return jax.profiler.StepTraceAnnotation(SPAN_PREFIX + "step",
                                            step_num=step)


class SimulatedFailure(_faults.InjectedFault):
    """A mid-step node death.  Subclasses the registry's InjectedFault so
    one ``except`` in the restart loop covers both the trainer's own
    injections and faults raised by deeper layers (checkpoint.save)."""


class StragglerDetected(RuntimeError):
    def __init__(self, step, step_time, median):
        super().__init__(
            f"step {step} took {step_time:.3f}s > "
            f"{median:.3f}s median x factor")
        self.step = step


@dataclasses.dataclass
class ElasticConfig:
    ckpt_every: int = 10
    max_restarts: int = 3
    straggler_factor: float = 3.0
    straggler_patience: int = 3
    straggler_window: int = 16
    fail_at_steps: tuple = ()      # legacy test hook -> train.step specs
    raise_on_straggler: bool = False


class ElasticTrainer:
    def __init__(self, *, make_step: Callable[[], Callable],
                 make_state: Callable[[], Any],
                 batches: Callable[[int], Iterable],
                 checkpointer: Checkpointer,
                 cfg: ElasticConfig | None = None,
                 state_shardings: Any = None,
                 faults: _faults.FaultPlan | None = None,
                 on_step: Callable | None = None):
        # on_step(step, loss, dt_s): host-side live-progress hook, fired
        # after each step's loss is materialized (drivers print from it;
        # it must not mutate training state).
        self.on_step = on_step
        self.make_step = make_step
        self.make_state = make_state
        self.batches = batches
        self.ckpt = checkpointer
        # NOTE: never a `cfg: ElasticConfig = ElasticConfig()` default —
        # a dataclass default in the signature is evaluated ONCE and
        # shared by every trainer in the process (a real aliasing hazard
        # the moment configs grow mutable state).
        self.cfg = cfg if cfg is not None else ElasticConfig()
        self.state_shardings = state_shardings
        self.faults = faults if faults is not None else _faults.FaultPlan()
        self.restarts = 0
        self.straggler_events: list[int] = []
        self._failspecs_synced = False

    # ------------------------------------------------------------------
    def _sync_failspecs(self):
        """Translate the legacy cfg.fail_at_steps shorthand onto the
        registry plan (once; re-reads cfg at run() so tests that swap
        cfg post-construction keep working)."""
        if self._failspecs_synced:
            return
        self._failspecs_synced = True
        if self.cfg.fail_at_steps:
            self.faults.add(_faults.FaultSpec(
                point=_faults.TRAIN_STEP, kind=_faults.RAISE,
                at_steps=tuple(self.cfg.fail_at_steps), max_fires=None))

    def _restore_or_init(self):
        latest = self.ckpt.latest_step()
        state = self.make_state()
        if latest is not None:
            state = self.ckpt.restore(latest, state, self.state_shardings)
            return state, latest
        return state, 0

    # ------------------------------------------------------------------
    def run(self, total_steps: int) -> dict:
        """Train until total_steps, surviving injected failures."""
        self._sync_failspecs()
        metrics_log = []
        with _faults.install(self.faults):
            return self._run(total_steps, metrics_log)

    def _run(self, total_steps: int, metrics_log: list) -> dict:
        # the trainer's plan is ambient for the whole run so deeper layers
        # (checkpoint.save, contract.dispatch) fire against it too; the
        # async checkpoint writer runs on a fresh thread context, so
        # save faults deterministically hit the SYNC save boundary
        while True:
            try:
                state, start = self._restore_or_init()
                step_fn = self.make_step()
                times: list[float] = []
                slow = 0
                feed = iter(self.batches(start))
                step = start
                while True:
                    with _span_step(step):
                        with _span("batch"):
                            item = next(feed, None)
                        if item is None or item[0] >= total_steps:
                            break
                        step, batch = item
                        t0 = time.perf_counter()
                        fault = self.faults.fire(_faults.TRAIN_STEP,
                                                 step=step)
                        if fault is not None:
                            if fault.kind == _faults.RAISE:
                                raise SimulatedFailure(
                                    f"injected at step {step}")
                            if fault.kind == _faults.LATENCY:
                                # inside the timed window: an injected
                                # straggler the watchdog must catch
                                time.sleep(fault.latency_s)
                        with _span("dispatch"):
                            state, metrics = step_fn(state, batch)
                        with _span("wait"):
                            jax.block_until_ready(metrics["loss"])
                        dt = time.perf_counter() - t0
                        # ---- straggler watchdog ----
                        if len(times) >= 4:
                            med = statistics.median(
                                times[-self.cfg.straggler_window:])
                            if dt > self.cfg.straggler_factor * med:
                                slow += 1
                                if slow >= self.cfg.straggler_patience:
                                    self.straggler_events.append(step)
                                    slow = 0
                                    if self.cfg.raise_on_straggler:
                                        raise StragglerDetected(step, dt,
                                                                med)
                            else:
                                slow = 0
                        times.append(dt)
                        with _span("log"):
                            metrics_log.append(
                                {"step": step,
                                 "loss": float(metrics["loss"])})
                            if self.on_step is not None:
                                self.on_step(step, metrics_log[-1]["loss"],
                                             dt)
                        if (step + 1) % self.cfg.ckpt_every == 0:
                            with _span("checkpoint"):
                                self.ckpt.save_async(step + 1, state)
                        step += 1
                with _span("checkpoint"):
                    self.ckpt.wait()
                    self.ckpt.save(total_steps, state)
                return {"state": state, "metrics": metrics_log,
                        "restarts": self.restarts,
                        "stragglers": self.straggler_events}
            except _faults.InjectedFault:
                self.restarts += 1
                self.ckpt.wait()
                if self.restarts > self.cfg.max_restarts:
                    raise
