"""A training cell, driven through the program's own entry.

Set-up builds one object, the launcher's compiled train step with its
state, as ``python -m repro.launch.train`` builds it (``train.build`` on
the arguments a user passes), except that the state is made from the seed
by one jitted call on the device. It drives that step through its first
three steps on the benchmark's own batches, reads what the output check
needs, and hands the same state to the measured window, which runs the step
back to back with a few seconds of steps dispatched ahead, so that a host
that stands still leaves the device fed. Nothing compiles inside the
window. After it: peak device memory, then the plain
reference (the program's state freed) and the comparison.

What belongs to the model's architecture comes from the cell's family
(``chipbench/families``): its named tensors, their place in the program's
parameter tree, the check of the program's widths, and the reference.

Checkpoint I/O and the launcher's ``RunSupervisor`` are outside the
window: a run saves nothing."""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import math
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import compare, data, scopes, spec, trace as trace_lib, weights
from chipbench.reference import adamw

CHECK_STEPS = 3        # set-up steps the output check reads
AHEAD_S = 5.0          # seconds of steps in flight behind the one waited on
BF16 = jnp.bfloat16


def annotate(name: str):
    """A host span on the profiler's clock (inert while not tracing)."""
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def seed_key(seed: int):
    """A raw threefry key from any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} out of range")
    return jnp.array([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)


def program_argv(cell: spec.Cell, precision: str | None = None) -> list:
    """The launcher's arguments for this cell: the traffic's, then the
    configuration's own ``launcher`` list (a cut its family needs)."""
    c, t = cell.config, cell.traffic
    argv = ["--arch", c["arch"], "--layers", str(c["num_hidden_layers"]),
            "--seq-len", str(t["seq_len"]),
            "--batch", str(t["rows_per_chip"] * t["dp"]),
            "--lr", repr(t["lr"]), "--warmup", str(t["warmup"]),
            "--steps", str(t["steps"]), "--b2", repr(t["b2"]),
            "--weight-decay", repr(t["weight_decay"])] + list(t["launcher"])
    argv += list(c.get("launcher", []))
    if t["dp"] > 1:
        argv += ["--dp", str(t["dp"]), "--zero"]
    if precision is not None:
        argv += ["--precision", precision]
    return argv


class TrainProgram:
    """The system under test: the launcher's train step and its state."""

    def __init__(self, cell: spec.Cell, precision: str | None = None):
        from repro.launch import train
        from repro.train import sharded

        self.cell = cell
        self.family = cell.family
        self.args = train.parse_args(program_argv(cell, precision))
        (self.cfg, self.model, self.opt, self.step_fn, _, self.mesh,
         _) = train.build(self.args)
        self.dims = self.family.dims_of(cell.config)
        self.table = self.family.shapes(self.dims)
        differ = self.family.check_widths(self.cfg, self.opt, self.dims,
                                          cell.traffic)
        if differ:
            raise spec.SpecError(
                f"program config {self.cfg.name} differs from the "
                f"configuration and traffic files: {differ} (program's, "
                f"files')")
        if not self.opt.policy.bucketing.enabled:
            raise spec.SpecError("the harness reads the bucketed state: "
                                 "the traffic must pass --bucketed")
        dp = cell.traffic["dp"]
        if self.mesh is None:
            self.devices = jax.devices()[:1]
            self.batch_sharding = None
            state_shardings = None
        else:
            self.devices = list(self.mesh.devices.flat)
            self.batch_sharding = NamedSharding(self.mesh, P("data", None))
            shape = jax.eval_shape(self._make_state, seed_key(0))
            state_shardings = sharded.named_shardings(
                shape, sharded.state_pspecs(shape, axis="data",
                                            zero_shard=dp > 1), self.mesh)
        self.init_state = jax.jit(self._make_state,
                                  out_shardings=state_shardings)
        self.compiled = None

    def _make_state(self, key):
        """The program's train state, its weights made from ``key``."""
        from repro.train import train_loop
        params = self.family.to_program(weights.generate(key, self.table,
                                                         BF16))
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        if (jax.tree_util.tree_structure(params)
                != jax.tree_util.tree_structure(want)
                or any(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                    lambda a, b: a.shape != b.shape or a.dtype != b.dtype,
                    params, want)))):
            raise spec.SpecError("the benchmark's tensors do not match "
                                 "the program's parameter tree")
        params, opt_state = self.opt.init_bucketed(params)
        return train_loop.TrainState(params, opt_state, None)

    def batch(self, tokens):
        b = {"tokens": tokens, "labels": tokens}
        if self.batch_sharding is not None:
            b = jax.device_put(b, self.batch_sharding)
        return b

    def compile(self, state, batch):
        self.compiled = self.step_fn.lower(state, batch).compile()
        return self.compiled

    def step(self, state, batch):
        return self.compiled(state, batch)

    # ----------------------------------------------------- state readouts
    @staticmethod
    def _tree(x, state):
        """A parameter-shaped role of the bucketed state, as a tree."""
        from repro.core import bucketing
        return bucketing.unbucket(x, state.params.layout)

    def grad_norms(self, state) -> dict:
        """The first gradient per tensor, from the first moment after step
        one: m₁ = (1 − β₁)·g, the factor in m's storage type."""
        @jax.jit
        def norms(state):
            m = self.family.from_program(self._tree(state.opt_state.m,
                                                    state))
            out = {}
            for k, v in m.items():
                c = jnp.asarray(1.0 - self.opt.b1, v.dtype).astype(jnp.float32)
                out[k] = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)
                                                     / c)))
            return out
        return {k: float(v) for k, v in norms(state).items()}

    def value(self, state):
        """The parameter value the optimizer moves, float32: θ + δθ under
        the Collage expansion, the master copy where there is one, else θ."""
        s = self.opt.policy.strategy
        theta = state.params.tree()
        f32 = lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), t)
        if s.uses_master_weights:
            return f32(self._tree(state.opt_state.master, state))
        if s.uses_expansion_params:
            return jax.tree_util.tree_map(
                jnp.add, f32(theta), f32(self._tree(state.opt_state.delta,
                                                    state)))
        return f32(theta)

    def change_norms(self, state, key_w) -> dict:
        """Per tensor, the norm of the parameters' change from the initial
        weights. Each initial tensor is made anew from ``key_w`` inside the
        computation that reads it, one tensor to a computation, so that no
        copy of the initial weights is held beside the state and the peak
        memory read after the window stays the step's own."""
        @functools.partial(jax.jit, static_argnums=2)
        def norm(state, key, name):
            w = self.family.from_program(self.value(state))[name]
            w0 = weights.tensor(key, self.table, name, BF16)
            return jnp.sqrt(jnp.sum(jnp.square(w - w0.astype(jnp.float32))))
        return {k: float(norm(state, key_w, k)) for k in sorted(self.table)}


def peak_bytes(stats: dict) -> int:
    """A device's peak HBM: its buffers at their peak plus the scratch the
    runtime reserves for the loaded programs, the step's temporaries
    (``peak_bytes_in_use`` leaves those out on the TPU runtime)."""
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def optimizer_bytes(state) -> int:
    """Bytes the optimizer kernel must move in one step on one chip."""
    from chipbench.counts import collage_update
    o = state.opt_state
    roles = [state.params.data, o.m, o.vhi, o.vlo, o.delta, o.master]
    total = 0
    for i, theta in enumerate(state.params.data):
        elems = math.prod(theta.sharding.shard_shape(theta.shape))
        fields = [r[i].dtype.itemsize for r in roles if r is not None]
        total += collage_update.bytes_moved(elems, fields,
                                            theta.dtype.itemsize)
    return total


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    seconds: float
    metrics: list          # each step's metrics, on the device
    ends: list             # host seconds from the start until each step
                           # was seen finished


def run_window(prog: TrainProgram, state, pool, seconds: float):
    """The measured window: the compiled step back to back over ``pool``,
    about ``AHEAD_S`` seconds of steps dispatched ahead of the one the host
    waits for. Once ``seconds`` have passed nothing more is sent; the window
    ends when every step sent has finished, and all of them count."""
    metrics, ends, sent = [], [], collections.deque()
    t0 = time.perf_counter()

    def finish_oldest():
        with annotate("block"):
            jax.block_until_ready(sent[0])
        metrics.append(sent.popleft())
        ends.append(time.perf_counter() - t0)

    with annotate("window"):
        while time.perf_counter() - t0 < seconds:
            with annotate("batch"):
                b = pool[(CHECK_STEPS + len(metrics) + len(sent)) % len(pool)]
            with annotate("dispatch"):
                state, met = prog.step(state, b)
            sent.append(met)
            ahead = max(1, int(AHEAD_S * len(ends) / ends[-1])) if ends else 1
            while len(sent) > ahead:
                finish_oldest()
        while sent:
            finish_oldest()
        with annotate("block"):
            jax.block_until_ready(state)
    t1 = time.perf_counter()
    return state, Window(t1 - t0, metrics, ends)


def reference_readings(cell: spec.Cell, key_w, check_tokens, devices) -> dict:
    """The plain reference of the cell's family over the set-up steps'
    batches."""
    fam, t = cell.family, cell.traffic
    dm = fam.dims_of(cell.config)
    opt = adamw.AdamW(lr=t["lr"], warmup=t["warmup"], total=t["steps"],
                      b1=t["b1"], b2=t["b2"], eps=t["eps"],
                      weight_decay=t["weight_decay"])
    mesh = jax.sharding.Mesh(devices, ("rows",))
    rows = NamedSharding(mesh, P("rows", None))
    rep = NamedSharding(mesh, P())
    toks = [jax.device_put(x, rows) for x in check_tokens]
    w0 = lambda: jax.device_put(weights.generate(key_w, fam.shapes(dm), BF16),
                                rep)
    return fam.run(w0, toks, opt, dm, mesh)


def keys(seed: int):
    """(weights key, data key) of a seed."""
    key = seed_key(seed)
    return jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)


def check_steps(prog: TrainProgram, state, pool, key_w):
    """The set-up steps, through the window's own call and feed, and what
    the output check reads of them: each step's loss, the first gradient
    (after step 1) and the change of the parameters (after step 2)."""
    read = {"losses": []}
    for i in range(CHECK_STEPS):
        state, met = prog.step(state, pool[i])
        read["losses"].append(float(met["loss"]))
        if i == 0:
            read["grad_norms"] = prog.grad_norms(state)
        if i == 1:
            read["change_norms"] = prog.change_norms(state, key_w)
    return jax.block_until_ready(state), read


def start(prog: TrainProgram, seed: int, fault=None):
    """Set-up of one seed on a built program: the state and the batch pool
    from the seed, the step compiled (once a program), then the set-up
    steps the output check reads. Returns (state, pool, tokens, readings).

    ``fault`` (tests and ``calibrate.py`` only) is applied once the step is
    compiled: ``fault(prog)`` may wrap its step, as a broken timed path
    would."""
    t = prog.cell.traffic
    key_w, key_d = keys(seed)
    state = prog.init_state(key_w)
    tokens = data.for_traffic(key_d, prog.dims.vocab, t, t["pool"])
    pool = [prog.batch(x) for x in tokens]
    if prog.compiled is None:
        prog.compile(state, pool[0])
    if fault is not None:
        fault(prog)
    state, read = check_steps(prog, state, pool, key_w)
    return state, pool, tokens, read


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, precision: str | None = None, fault=None) -> dict:
    """One run of a training cell; the result's fields before printing."""
    t = cell.traffic
    prog = TrainProgram(cell, precision)
    state, pool, tokens, prog_read = start(prog, seed, fault)
    on_chip = prog.devices[0].platform == "tpu"

    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        setup_s = time.perf_counter() - t_start
        with trace_lib.recording(tdir) if trace else contextlib.nullcontext():
            state, win = run_window(prog, state, pool, seconds)
        memory = [d.memory_stats() for d in prog.devices] if on_chip else []
        peak = max((peak_bytes(m) for m in memory), default=0)
        events = trace_lib.load(tdir) if trace else None
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    losses = [float(m["loss"]) for m in win.metrics]
    last = {k: float(v) for k, v in win.metrics[-1].items()}
    window_s, ends, opt_bytes = win.seconds, win.ends, optimizer_bytes(state)
    text = prog.compiled.as_text()
    kernel_ops = trace_lib.kernel_ops(text, t["kernels"])
    missing = len(set(t["kernels"]) - set(kernel_ops.values()))
    phase_map = scopes.phase_map(text) if trace else None
    scope_paths = scopes.scope_paths(text) if trace else None
    del text
    devices = prog.devices
    check_tokens = tokens[:CHECK_STEPS]
    dims = prog.dims
    del state, pool, win, prog, tokens

    ref = reference_readings(cell, keys(seed)[0], check_tokens, devices)
    values = compare.readings(prog_read, ref)
    failed = sum(1 for x in losses if not math.isfinite(x))
    values["failed_steps"] = failed
    if on_chip:      # off the chip the kernels run interpreted, unnamed
        values["kernels_missing"] = missing
    correct, checks = compare.judge(values, cell.limits)
    return {"correct": correct, "attempted": len(losses), "failed": failed,
            "setup_s": setup_s, "window_s": window_s,
            "tokens_per_step": t["rows_per_chip"] * t["dp"] * t["seq_len"],
            "peak_bytes": peak, "devices": devices, "checks": checks,
            "readings": {"program": prog_read, "reference": ref,
                         "values": values},
            "events": events, "kernel_ops": kernel_ops,
            "phase_map": phase_map, "scope_paths": scope_paths,
            "optimizer_bytes": opt_bytes, "family": cell.family,
            "dims": dims, "last_step": last,
            "memory": memory, "step_ends": ends}
