"""Pipeline parallelism as a schedule-as-data IR (DESIGN.md §9).

A schedule is DATA, not control flow: :func:`make_schedule` compiles a
named policy (``gpipe`` | ``1f1b`` | ``interleaved``) into per-tick
instruction arrays — for every (tick, stage) cell, which microbatch runs
its forward, which runs its backward, and which activation-stash slots
are read/written — plus the comm-readiness metadata (at which tick each
gradient bucket class closes). One interpreter (:func:`run_schedule`)
executes ANY schedule inside the caller's shard_map as a single
``lax.scan`` over ticks; generators do all slot allocation and
dependency validation host-side with plain numpy.

Why the backward is explicit: the legacy GPipe path (:func:`stage_schedule`,
kept below for the standalone ``pipeline_apply`` wrapper) gets its backward
for free from AD transposing the forward scan — which forces the backward
to mirror the forward (no 1F1B interleaving) and makes every body gradient
arrive S-fold through the transposed closing psum (the PR-5 ``fix_body``
lesson). The interpreter instead recomputes each chunk at its Bwd tick
(``jax.vjp`` at the stashed input — activation-checkpointing semantics) and
computes the head loss + output cotangent inline at final-chunk Bwd ticks.
Nothing is differentiated THROUGH the schedule, so there is no transposed
collective and no hidden gradient scale — per-schedule parity is pinned by
tests/test_sharded_engine.py against the unpipelined oracle.

Execution model (what the cost model charges for): every tick traces one
masked forward unit and one masked backward unit — a bubble slot burns the
same compute as a real one (SPMD lax.scan cannot skip work per device).
Makespan is therefore ``T · (fwd+bwd)/V`` and the bubble fraction is
``1 − M·V/T`` (analysis/cost_model.py): GPipe pays its idle backward units
during the forward phase and vice versa, 1F1B fills both units in steady
state, and interleaving divides the warmup/drain ramps by V.

Schedules:

  * ``gpipe``   — all forwards, then all backwards. Stash: M slots.
  * ``1f1b``    — stage s runs min(M, S−s) warmup forwards, then alternates
    Bwd/Fwd (both units active per tick in steady state). Same-tick-count
    asymptote as GPipe per classic analysis, but under the masked-tick
    model its span T ≈ M + S < T_gpipe ≈ 2(M+S) and its stash is
    min(M, S−s) slots instead of M — both claims asserted structurally.
  * ``interleaved`` — V virtual chunks per device, chunk c on device
    c mod S (round-robin): the ring ppermute stays a uniform +1 shift and
    a (L,…) layer stack reshaped to (V, S, L/(S·V), …) sharded on dim 1
    IS the canonical layer order. Megatron-style ordering (microbatch
    groups of S, chunks inner), warmup 2(S−1−s) + (V−1)·S + 1; requires
    M % S == 0.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

SCHEDULES = ("gpipe", "1f1b", "interleaved")


# ==========================================================================
# Schedule IR
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class Schedule:
    """Per-tick instruction program for ``run_schedule``.

    All arrays are host-side numpy, shape (T, S), int32, −1 = no-op.
    ``f_*`` drive the forward unit of a tick, ``b_*`` the backward unit;
    ``*_wslot`` name the stash slot into which THIS tick's ppermute
    arrival is written (−1 = discard — the wire carries garbage).

    For the forward of (chunk c, micro m): ``f_slot`` is the stash slot
    holding its input activation (−1 ⇒ c == 0, read xs[micro]); the same
    slot is read again at the Bwd tick (``b_xslot``) for the VJP
    recompute, then freed. ``b_dyslot`` holds the arrived output
    cotangent (−1 ⇒ c == C−1: the head loss/cotangent is computed
    inline). Slot indices are generator-allocated with liveness checking
    (:func:`_allocate_slots`); ``n_fwd_slots``/``n_bwd_slots`` size the
    stashes — the per-schedule activation-memory claim, asserted by
    tests."""
    name: str
    n_stages: int
    n_micro: int
    n_virtual: int
    f_chunk: np.ndarray
    f_micro: np.ndarray
    f_slot: np.ndarray
    f_wslot: np.ndarray
    b_chunk: np.ndarray
    b_micro: np.ndarray
    b_xslot: np.ndarray
    b_dyslot: np.ndarray
    b_wslot: np.ndarray
    n_fwd_slots: int
    n_bwd_slots: int
    # tick AFTER which each gradient bucket class is complete (all
    # contributing Bwd ticks executed) — drives the comm-launch order and
    # the overlap cost model
    comm_ready: dict

    @property
    def n_chunks(self) -> int:
        return self.n_stages * self.n_virtual

    @property
    def n_ticks(self) -> int:
        return int(self.f_chunk.shape[0])

    def stats(self) -> dict:
        """Structural summary for tests and analysis.cost_model."""
        T, M, V = self.n_ticks, self.n_micro, self.n_virtual
        return {
            "name": self.name, "n_stages": self.n_stages, "n_micro": M,
            "n_virtual": V, "n_ticks": T,
            "n_fwd_slots": self.n_fwd_slots,
            "n_bwd_slots": self.n_bwd_slots,
            # masked-tick bubble: every tick costs (fwd+bwd)/V on every
            # device; ideal is M·V ticks (both units busy throughout)
            "bubble_fraction": 1.0 - (M * V) / T,
            "comm_ready": dict(self.comm_ready),
        }


def _orders(name: str, S: int, M: int, V: int):
    """Per-device forward/backward op orderings + warmup depths.

    Returns (fwd_orders, bwd_orders, warmup): op = (chunk, micro);
    ``warmup[s]`` bounds the device's forwards-in-flight (fwd issued −
    bwd issued) — the 1F1B memory cap; M·V disables the cap (GPipe)."""
    fwd, bwd, warm = [], [], []
    for s in range(S):
        if V == 1:
            f = [(s, m) for m in range(M)]
            b = list(f)
        else:
            if M % S:
                raise ValueError(
                    f"interleaved schedule needs n_micro % n_stages == 0, "
                    f"got M={M}, S={S}")
            f = [(v * S + s, g * S + i)
                 for g in range(M // S)
                 for v in range(V)
                 for i in range(S)]
            b = [(v * S + s, g * S + i)
                 for g in range(M // S)
                 for v in reversed(range(V))
                 for i in range(S)]
        fwd.append(f)
        bwd.append(b)
        if name == "gpipe":
            warm.append(M * V)
        elif name == "1f1b":
            warm.append(min(M, S - s))
        else:  # interleaved
            warm.append(min(M * V, 2 * (S - 1 - s) + (V - 1) * S + 1))
    return fwd, bwd, warm


def _simulate(name: str, S: int, M: int, V: int):
    """Dependency-driven tick simulation → (rows, fwd_tick, bwd_tick).

    Each tick a device may issue one forward AND one backward (its two
    units), strictly in its policy order, gated by dataflow: Fwd(c, m)
    needs the arrival of Fwd(c−1, m) by the end of an earlier tick;
    Bwd(c, m) needs its own Fwd done earlier plus (c < C−1) the arrival
    of Bwd(c+1, m)'s input cotangent. The backward unit is considered
    first so a completed Bwd frees its in-flight slot for the same-tick
    forward (the 1F1B steady state). GPipe additionally holds every
    backward until the device's forward list is exhausted."""
    C = S * V
    fwd_orders, bwd_orders, warm = _orders(name, S, M, V)
    fwd_tick: dict = {}
    bwd_tick: dict = {}
    fp, bp = [0] * S, [0] * S
    rows = []
    t = 0
    while any(fp[s] < len(fwd_orders[s]) or bp[s] < len(bwd_orders[s])
              for s in range(S)):
        progress = False
        row = []
        for s in range(S):
            bop = None
            if bp[s] < len(bwd_orders[s]) and \
                    (name != "gpipe" or fp[s] == len(fwd_orders[s])):
                c, m = bwd_orders[s][bp[s]]
                ok = (c, m) in fwd_tick and fwd_tick[(c, m)] < t
                if c < C - 1:
                    ok = ok and (c + 1, m) in bwd_tick \
                        and bwd_tick[(c + 1, m)] < t
                if ok:
                    bop = (c, m)
                    bwd_tick[(c, m)] = t
                    bp[s] += 1
                    progress = True
            fop = None
            if fp[s] < len(fwd_orders[s]) and fp[s] - bp[s] < warm[s]:
                c, m = fwd_orders[s][fp[s]]
                if c == 0 or ((c - 1, m) in fwd_tick
                              and fwd_tick[(c - 1, m)] < t):
                    fop = (c, m)
                    fwd_tick[(c, m)] = t
                    fp[s] += 1
                    progress = True
            row.append((fop, bop))
        if not progress:
            raise AssertionError(
                f"schedule {name!r} deadlocked at tick {t} "
                f"(S={S}, M={M}, V={V}, fp={fp}, bp={bp})")
        rows.append(row)
        t += 1
    return rows, fwd_tick, bwd_tick


def _allocate_slots(events):
    """Greedy first-fit slot allocation with liveness checking.

    ``events``: [(arrival_tick, free_tick, key)] for one device — the
    value is written at the END of arrival_tick and last read at the
    START of free_tick, so a slot is reusable by an arrival at
    tick ≥ its previous free_tick. Returns ({key: slot}, n_slots)."""
    slots: list = []  # free_tick per slot
    assign = {}
    for arrival, free, key in sorted(events):
        for i, slot_free in enumerate(slots):
            if arrival >= slot_free:
                slots[i] = free
                assign[key] = i
                break
        else:
            assign[key] = len(slots)
            slots.append(free)
    return assign, len(slots)


def make_schedule(name: str, *, n_stages: int, n_micro: int,
                  n_virtual: int = 1) -> Schedule:
    """Compile a named schedule into its instruction-array IR."""
    if name not in SCHEDULES:
        raise ValueError(f"unknown schedule {name!r}; one of {SCHEDULES}")
    if name != "interleaved" and n_virtual != 1:
        raise ValueError(f"n_virtual={n_virtual} requires the interleaved "
                         f"schedule (got {name!r})")
    if name == "interleaved" and n_virtual < 2:
        raise ValueError("interleaved schedule needs n_virtual >= 2")
    S, M, V = n_stages, n_micro, n_virtual
    C = S * V
    rows, fwd_tick, bwd_tick = _simulate(name, S, M, V)
    T = len(rows)

    # -- validate: every op exactly once, forward strictly before backward
    want = {(c, m) for c in range(C) for m in range(M)}
    assert set(fwd_tick) == want and set(bwd_tick) == want, \
        (name, S, M, V, len(fwd_tick), len(bwd_tick))
    for key in want:
        assert fwd_tick[key] < bwd_tick[key], (name, key)

    # -- slot allocation (per device; stash shape is the max — SPMD)
    f_assign: dict = {}
    b_assign: dict = {}
    n_f = n_b = 1
    for s in range(S):
        fev = [(fwd_tick[(c - 1, m)], bwd_tick[(c, m)], (c, m))
               for (c, m) in fwd_tick
               if c % S == s and c > 0]
        a, n = _allocate_slots(fev)
        f_assign.update(a)
        n_f = max(n_f, n)
        bev = [(bwd_tick[(c + 1, m)], bwd_tick[(c, m)], (c, m))
               for (c, m) in bwd_tick
               if c % S == s and c < C - 1]
        a, n = _allocate_slots(bev)
        b_assign.update(a)
        n_b = max(n_b, n)

    # -- instruction arrays
    arrs = {k: np.full((T, S), -1, np.int32)
            for k in ("f_chunk", "f_micro", "f_slot", "f_wslot", "b_chunk",
                      "b_micro", "b_xslot", "b_dyslot", "b_wslot")}
    for t, row in enumerate(rows):
        for s, (fop, bop) in enumerate(row):
            if fop is not None:
                c, m = fop
                arrs["f_chunk"][t, s] = c
                arrs["f_micro"][t, s] = m
                if c > 0:
                    arrs["f_slot"][t, s] = f_assign[(c, m)]
                # the arrival this send produces: device s+1 stashes it
                if c < C - 1:
                    arrs["f_wslot"][t, (s + 1) % S] = f_assign[(c + 1, m)]
            if bop is not None:
                c, m = bop
                arrs["b_chunk"][t, s] = c
                arrs["b_micro"][t, s] = m
                if c > 0:
                    arrs["b_xslot"][t, s] = f_assign[(c, m)]
                if c < C - 1:
                    arrs["b_dyslot"][t, s] = b_assign[(c, m)]
                if c > 0:
                    arrs["b_wslot"][t, (s - 1) % S] = b_assign[(c - 1, m)]

    # -- bucket-class readiness: last contributing Bwd tick + 1
    comm_ready = {
        "head": max(bwd_tick[(C - 1, m)] for m in range(M)) + 1,
        "stage": max(bwd_tick.values()) + 1,
        "embed": max(bwd_tick[(0, m)] for m in range(M)) + 1,
    }
    return Schedule(name=name, n_stages=S, n_micro=M, n_virtual=V,
                    n_fwd_slots=n_f, n_bwd_slots=n_b, comm_ready=comm_ready,
                    **arrs)


# ==========================================================================
# the interpreter
# ==========================================================================

def run_schedule(sched: Schedule, body_fn: Callable, head_loss_fn: Callable,
                 chunk_params, head_params, xs, labels, *, axis: str):
    """Execute a Schedule inside the caller's shard_map (axis size S).

    ``body_fn(p_chunk, x) → (y, aux)`` applies one chunk's layer stack to
    one microbatch activation x (mb, L, D); ``chunk_params`` leaves carry
    a leading (V, …) local-chunk dim. ``head_loss_fn(head_params, y,
    labels_m) → ce_m`` is the per-microbatch head loss (final norm + lm
    head + token CE), computed inline at final-chunk Bwd ticks.
    ``xs`` (M, mb, L, D) are the embedded microbatch inputs (replicated;
    only chunk-0 ticks read them), ``labels`` (M, mb, L).

    Every gradient is produced explicitly — there is NO AD through the
    schedule, hence no transposed-psum gradient scale to fix up:

      * ``g_chunks``: (V, …)-leaved f32 tree — this device's chunk grads
        (stage-local, disjoint across devices: reduce over dp only);
      * ``g_head``: f32 tree like head_params — nonzero ONLY on the
        device owning chunk C−1 (psum over the pipe axis recovers it);
      * ``dxs``: (M, mb, L, D) f32 cotangents of xs — nonzero ONLY on the
        device owning chunk 0; feed them to the embedding pullback, then
        psum over the pipe axis;
      * ``ce``/``aux``: f32 scalar SUMS of per-micro CE (last-chunk
        device only) and per-(chunk, micro) MoE aux (every device's own
        chunks) — psum over pipe, divide by n_micro.

    The returned loss decomposition matches train_loop.make_accum_grads
    microbatch-for-microbatch: each ce_m is normalized by its OWN token
    count, cotangents are scaled 1/M, aux cotangent is the constant
    AUX_LOSS_COEF/M per (chunk, micro)."""
    from repro.models.model import AUX_LOSS_COEF

    S, M, V = sched.n_stages, sched.n_micro, sched.n_virtual
    C = sched.n_chunks
    stage = jax.lax.axis_index(axis)
    act = xs.dtype
    mb_shape = xs.shape[1:]
    inv_M = jnp.float32(1.0 / M)

    perm_fwd = [(i, (i + 1) % S) for i in range(S)]
    perm_bwd = [(i, (i - 1) % S) for i in range(S)]

    inst = {k: jnp.asarray(getattr(sched, k))
            for k in ("f_chunk", "f_micro", "f_slot", "f_wslot", "b_chunk",
                      "b_micro", "b_xslot", "b_dyslot", "b_wslot")}

    def pick(p, idx):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.clip(idx, 0, V - 1), keepdims=False), p)

    def row_set(stash, slot, val):
        i = jnp.maximum(slot, 0)
        row = jnp.where(slot >= 0, val, stash[i])
        return stash.at[i].set(row)

    def tick(carry, ins):
        fstash, bstash, gacc, hacc, dxs, ce, aux = carry
        fc = ins["f_chunk"][stage]
        fm = jnp.maximum(ins["f_micro"][stage], 0)
        fs = ins["f_slot"][stage]
        bc = ins["b_chunk"][stage]
        bm = jnp.maximum(ins["b_micro"][stage], 0)
        bx = ins["b_xslot"][stage]
        bdy = ins["b_dyslot"][stage]
        valid_b = bc >= 0
        is_last = valid_b & (bc == C - 1)

        # ---- forward unit (masked: bubble ticks chew stale activations)
        x_f = jnp.where(fc == 0,
                        jax.lax.dynamic_index_in_dim(xs, fm, keepdims=False),
                        fstash[jnp.maximum(fs, 0)])
        y, _ = body_fn(pick(chunk_params, fc // S), x_f)

        # ---- backward unit: VJP recompute at the stashed input
        x_b = jnp.where(bc == 0,
                        jax.lax.dynamic_index_in_dim(xs, bm, keepdims=False),
                        fstash[jnp.maximum(bx, 0)])
        (y_b, _aux_b), pull = jax.vjp(body_fn, pick(chunk_params, bc // S),
                                      x_b)
        lab = jax.lax.dynamic_index_in_dim(labels, bm, keepdims=False)
        ce_m, (g_hp, dy_head) = jax.value_and_grad(
            head_loss_fn, argnums=(0, 1))(head_params, y_b, lab)
        dy = jnp.where(is_last,
                       (dy_head.astype(jnp.float32) * inv_M).astype(act),
                       bstash[jnp.maximum(bdy, 0)])
        dy = jnp.where(valid_b, dy, jnp.zeros_like(dy))
        aux_ct = jnp.where(valid_b, jnp.float32(AUX_LOSS_COEF) * inv_M,
                           jnp.float32(0.0))
        dp, dx = pull((dy, aux_ct))

        # ---- accumulate (zero cotangents ⇒ dp, dx are exact zeros)
        v_b = jnp.clip(bc // S, 0, V - 1)
        gacc = jax.tree_util.tree_map(
            lambda a, d: a.at[v_b].add(d.astype(jnp.float32)), gacc, dp)
        hscale = jnp.where(is_last, inv_M, jnp.float32(0.0))
        hacc = jax.tree_util.tree_map(
            lambda h, g: h + g.astype(jnp.float32) * hscale, hacc, g_hp)
        dx0 = jnp.where(valid_b & (bc == 0), dx, jnp.zeros_like(dx))
        dxs = dxs.at[bm].add(dx0.astype(jnp.float32))
        ce = ce + jnp.where(is_last, ce_m.astype(jnp.float32), 0.0)
        aux = aux + jnp.where(valid_b, _aux_b.astype(jnp.float32), 0.0)

        # ---- ring shifts; receivers discard unscheduled arrivals
        y_in = jax.lax.ppermute(y, axis, perm_fwd)
        dx_in = jax.lax.ppermute(dx, axis, perm_bwd)
        fstash = row_set(fstash, ins["f_wslot"][stage], y_in)
        bstash = row_set(bstash, ins["b_wslot"][stage],
                         dx_in.astype(act))
        return (fstash, bstash, gacc, hacc, dxs, ce, aux), None

    carry = (
        jnp.zeros((sched.n_fwd_slots,) + mb_shape, act),
        jnp.zeros((sched.n_bwd_slots,) + mb_shape, act),
        jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), chunk_params),
        jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), head_params),
        jnp.zeros(xs.shape, jnp.float32),
        jnp.float32(0.0),
        jnp.float32(0.0),
    )
    carry, _ = jax.lax.scan(tick, carry, inst)
    _, _, gacc, hacc, dxs, ce, aux = carry
    return {"g_chunks": gacc, "g_head": hacc, "dxs": dxs,
            "ce": ce, "aux": aux}


# ==========================================================================
# legacy GPipe forward scan (standalone pipeline_apply path)
# ==========================================================================

def split_stages(stacked_params, n_stages: int):
    """(L, ...) layer-stacked leaves → (S, L/S, ...) for stage sharding."""
    def f(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])
    return jax.tree_util.tree_map(f, stacked_params)


def split_virtual(stacked_params, n_stages: int, n_virtual: int):
    """(L, ...) leaves → (V, S, L/(S·V), ...) round-robin chunk layout.

    Chunk c = v·S + s lives at [v, s] — flattening (v, s, k) recovers the
    canonical layer order, so sharding dim 1 over the pipe axis gives
    device s exactly its interleaved chunks {s, S+s, …} with no
    permutation (DESIGN.md §9)."""
    C = n_stages * n_virtual

    def f(x):
        L = x.shape[0]
        assert L % C == 0, (L, n_stages, n_virtual)
        return x.reshape(n_virtual, n_stages, L // C, *x.shape[1:])
    return jax.tree_util.tree_map(f, stacked_params)


def stage_schedule(body_fn: Callable, stage_params, xs_local, *, axis: str,
                   n_stages: int, with_aux: bool = False):
    """Per-device GPipe FORWARD schedule (legacy path): MUST run inside a
    shard_map with named ``axis`` of size ``n_stages``. Kept for
    ``pipeline_apply`` and differentiability tests; the train engine now
    executes :func:`run_schedule` instead. CAUTION: the closing psums
    transpose to psum under ``check_rep=False`` — every backward path
    through this schedule delivers gradients S-fold; rescale by
    1/n_stages (the PR-5 lesson, now documented in the DESIGN.md §9
    fixup table)."""
    S = n_stages
    n_micro = xs_local.shape[0]
    n_ticks = n_micro + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]
    stage = jax.lax.axis_index(axis)
    zero = jnp.zeros_like(xs_local[0])

    def tick(carry, t):
        buf = carry
        feed = jnp.where(t < n_micro,
                         xs_local[jnp.minimum(t, n_micro - 1)], zero)
        inp = jnp.where(stage == 0, feed, buf)
        res = body_fn(stage_params, inp)
        out, aux = res if with_aux else (res, jnp.zeros((), jnp.float32))
        nxt = jax.lax.ppermute(out, axis, perm)
        emit = jnp.where((stage == S - 1) & (t >= S - 1), out, zero)
        real = (t >= stage) & (t - stage < n_micro)
        aux = jnp.where(real, aux, jnp.zeros_like(aux))
        return nxt, (emit, aux)

    _, (emits, auxes) = jax.lax.scan(tick, zero, jnp.arange(n_ticks))
    outs = jax.lax.psum(emits[S - 1:], axis)
    if not with_aux:
        return outs
    return outs, jax.lax.psum(jnp.sum(auxes), axis)


def pipeline_apply(body_fn: Callable, staged_params, x_micro, *,
                   mesh: Mesh, axis: str = "pod"):
    """Run x_micro (n_micro, mb, L, D) through the S-stage pipeline.

    body_fn(stage_params, x) applies that stage's layer chunk (stage_params
    leaves have the (L/S, ...) layer dim). Returns (n_micro, mb, L, D)."""
    S = mesh.shape[axis]

    def per_stage(params_local, xs_local):
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        return stage_schedule(body_fn, params_local, xs_local,
                              axis=axis, n_stages=S)

    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P(),
                       check_vma=False)
    return fn(staged_params, x_micro)
