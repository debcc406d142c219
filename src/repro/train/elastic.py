"""Fault tolerance & elasticity: restart driver, straggler hooks.

What is real here vs simulated (single-host container — DESIGN.md §4):
  * REAL: crash-consistent checkpoints (atomic rename + checksums), restore
    onto a *different* mesh shape (elastic re-scale), bitwise-identical
    resume (counter-based data pipeline ⇒ no iterator replay), all tested.
  * SIMULATED/INTERFACE-ONLY: heartbeat monitoring and straggler detection
    run in-process against injected fault hooks; on a real cluster the same
    `RunSupervisor` wraps `jax.distributed` health signals. The policy logic
    (deadline → checkpoint-restore → re-mesh) is the deployable part.

Straggler mitigation policy (1000+ node scale):
  1. per-step deadline = p99(recent step times) × slack (default 3×);
  2. a missed deadline on a step that nonetheless COMPLETED keeps the
     completed state (work is never discarded for lateness) and records the
     faulting step in ``recoveries``/``stragglers`` — the re-mesh policy
     (exclude the slow host, relaunch with a smaller `data` axis) keys off
     these incident records; only a real crash restores the last
     checkpoint — the counter-based data sharding re-slices automatically;
  3. recovered hosts rejoin at the next checkpoint boundary (up-scale).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax

from repro.train import checkpoint as ckpt_lib

# Runtime statuses no checkpoint restore can cure: the device is out of
# memory, or XLA/Mosaic refused the program. Restoring and retrying would
# fail the same way forever, so they propagate.
_FATAL_STATUS = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT", "UNIMPLEMENTED")


def _fatal(e: BaseException) -> bool:
    return isinstance(e, jax.errors.JaxRuntimeError) \
        and str(e).lstrip().startswith(_FATAL_STATUS)


@dataclasses.dataclass
class SupervisorConfig:
    ckpt_dir: str
    ckpt_every: int = 100     # ≤ 0: never checkpoint (nothing to restore)
    keep_last: int = 3
    deadline_slack: float = 3.0
    min_step_time: float = 1e-3
    trace_dir: Optional[str] = None   # profile steps trace_steps[0]..[1]−1
    trace_steps: tuple = (0, 0)


class RunSupervisor:
    """Drives train steps with checkpointing + failure recovery.

    ``fault_hook(step)`` (tests) may raise to simulate a host crash; the
    supervisor restores and continues, and records every recovery. Device
    out-of-memory and compile refusals (``_FATAL_STATUS``) are not crashes
    a restore can cure: they propagate.

    ``recoveries`` records the FAULTING step of every incident (crash or
    straggler) — not the checkpoint step it rolled back to, which is what
    the old behaviour logged and which made incident forensics impossible
    (every recovery within one ckpt window looked identical). Stragglers —
    steps that finish late but *successfully* — keep their completed state:
    rolling a finished step back to the last checkpoint (the old behaviour)
    discarded up to ``ckpt_every`` steps of work on every deadline miss,
    turning a transient slow host into a repeated loss of progress. Only
    real crashes (exceptions out of the step) restore from checkpoint."""

    def __init__(self, cfg: SupervisorConfig, *,
                 fault_hook: Optional[Callable[[int], None]] = None):
        self.cfg = cfg
        self.fault_hook = fault_hook
        self.recoveries: list[int] = []     # faulting step per incident
        self.stragglers: list[int] = []     # subset: deadline misses
        self.step_times: list[float] = []

    def deadline(self) -> float:
        if len(self.step_times) < 5:
            return float("inf")
        recent = sorted(self.step_times[-50:])
        p99 = recent[min(len(recent) - 1, int(len(recent) * 0.99))]
        return max(p99, self.cfg.min_step_time) * self.cfg.deadline_slack

    def run(self, state, train_step, batch_fn, n_steps: int,
            start_step: int = 0, template=None):
        """Run to ``n_steps``, checkpointing and recovering on faults.

        Each iteration is a profiler step (``StepTraceAnnotation("train")``)
        holding the host spans ``repro.batch`` and ``repro.checkpoint``;
        with ``cfg.trace_dir`` the profiler records steps
        ``cfg.trace_steps`` = (A, B), A..B−1, into that directory.

        template: pytree template for elastic restore (defaults to state)."""
        step = start_step
        last_metrics = None
        first, end = self.cfg.trace_steps
        tracing = traced = False
        try:
            while step < n_steps:
                if (self.cfg.trace_dir and not traced
                        and first <= step < end):
                    jax.profiler.start_trace(self.cfg.trace_dir)
                    tracing = traced = True
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    state, step, last_metrics = self._step(
                        state, step, train_step, batch_fn, n_steps,
                        template, last_metrics)
                if tracing and step >= end:
                    jax.profiler.stop_trace()
                    tracing = False
        finally:
            if tracing:
                jax.profiler.stop_trace()
        return state, step, last_metrics

    def _step(self, state, step, train_step, batch_fn, n_steps, template,
              last_metrics):
        """One iteration of ``run``: (state, next step, metrics)."""
        t0 = time.monotonic()
        try:
            if self.fault_hook is not None:
                self.fault_hook(step)
            with jax.profiler.TraceAnnotation("repro.batch"):
                batch = batch_fn(step)
            state, last_metrics = train_step(state, batch)
        except (RuntimeError, TimeoutError) as e:  # real crash
            if _fatal(e):
                raise
            restore_step = ckpt_lib.latest_step(self.cfg.ckpt_dir)
            if restore_step is None:
                raise RuntimeError("fault before first checkpoint") from e
            self.recoveries.append(step)       # the FAULTING step
            # layout-elastic: migrates bucketed states whose bucket
            # partitioning changed with the re-scaled mesh (no-op for
            # tree-layout states)
            state, extra = ckpt_lib.restore_bucketed(
                self.cfg.ckpt_dir, restore_step, template or state)
            return state, extra["step"], last_metrics
        dt = time.monotonic() - t0
        deadline = self.deadline()
        if dt > deadline:
            # late but SUCCESSFUL: the new state is valid — keep it and
            # flag the incident (re-mesh policy hooks read these). The
            # sample enters the p99 window CLAMPED to the deadline: a
            # one-off outlier can't poison the window, but a genuine
            # regime change (re-meshed smaller, slower hosts) ratchets
            # the deadline up by ~slack× per window refresh instead of
            # flagging every step forever.
            self.recoveries.append(step)
            self.stragglers.append(step)
            self.step_times.append(deadline)
        else:
            self.step_times.append(dt)
        step += 1
        if self.cfg.ckpt_every > 0 and (step % self.cfg.ckpt_every == 0
                                        or step == n_steps):
            with jax.profiler.TraceAnnotation("repro.checkpoint"):
                ckpt_lib.save(self.cfg.ckpt_dir, step, state,
                              keep_last=self.cfg.keep_last,
                              extra={"step": step})
        return state, step, last_metrics
