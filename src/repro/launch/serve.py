"""Serving launcher: jit-resident generation engines with request batching.

Two engines share the model's jit-resident decode seam (DESIGN.md §6/§10):

* ``GenerationEngine`` — CLOSED-batch: a fixed request list is bucketed,
  padded, and each batch runs ``Model.generate`` to its full gen length in
  one jitted program. EOS / per-request budgets freeze finished rows, but
  their scan slots are still paid for — the engine now reports
  ``tokens_generated`` vs ``tokens_padded`` so that cost is measurable.
* ``ContinuousEngine`` — OPEN-stream continuous batching: a fixed
  ``(max_slots, cache_len)`` slot-pool KV arena (``Model.SlotState``)
  driven by a host scheduler that interleaves bucketed prefill launches
  (``prefill_into`` scatters new rows into free slots) with fixed-shape
  ``decode_segment`` launches, retiring finished rows and refilling their
  slots BETWEEN segments — no recompile under churn; admission is
  controlled by a token budget; outputs stream per request as rows finish.

``ContinuousEngine`` optionally runs **speculative decoding** on the same
slot-pool seam (DESIGN.md §11): a draft model proposes ``spec_k`` tokens
per live slot (one fixed-shape scan over a paired draft cache pool), then
ONE batched target verify forward over ``(max_slots, spec_k + 1)`` commits
the accepted prefix of every slot via the existing ``n_gen``-delta
protocol and rolls the rejected suffix back structurally (``pos`` is the
only rollback — stale KV rows beyond it are masked out and re-written).
Greedy speculative output is bit-identical to non-speculative greedy.

Both engines speak the unified API from ``repro.launch.api``:
``SamplingParams`` (legacy loose kwargs still work via a deprecation
shim), ``Request``/``RequestResult`` through ``engine.run``, the typed
``AdmissionError``/``CapabilityError``/``PoolError`` taxonomy, and the
``make_engine`` factory.

Compile count stays bounded in both: one executable per prompt bucket
(prefill / closed-batch generate) plus exactly one decode-segment program
(speculative: one draft-propose plus one verify program).

  PYTHONPATH=src python -m repro.launch.serve --arch gpt-tiny --smoke \
      --requests 16 --gen 32 --temperature 0.8 --top-k 40
  PYTHONPATH=src python -m repro.launch.serve --arch gpt-tiny --smoke \
      --continuous --requests 32 --slots 8 --seg-len 8 --arrival-rate 0.5
  PYTHONPATH=src python -m repro.launch.serve --arch gpt-tiny --smoke \
      --continuous --speculative-draft layers:1 --spec-k 4 --requests 32
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import time
from collections import deque
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, with_layers
from repro.data.synthetic import SyntheticCorpus
from repro.launch import compile_cache
from repro.launch.api import (AdmissionError, CapabilityError, PoolError,
                              Request, RequestResult, SamplingParams,
                              ServeError, make_engine)
from repro.models.model import Model, build_model

__all__ = [
    "Request", "RequestResult", "SamplingParams", "ServeError",
    "AdmissionError", "CapabilityError", "PoolError", "make_engine",
    "SlotPool", "GenerationEngine", "ContinuousEngine", "draft_from_target",
    "main",
]


def _bucket_len(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class SlotPool:
    """Host-side free/alloc bitmap for the slot arena.

    Pure bookkeeping — the device-side liveness lives in
    ``SlotState.active/done``; this class decides WHICH slot a new request
    lands in and guards the scheduler invariants (no double-alloc, no
    double-free, no lost slots), which ``tests/test_slot_pool.py`` hammers
    under randomized churn."""

    def __init__(self, n_slots: int):
        if n_slots <= 0:
            raise AdmissionError(
                f"n_slots must be positive, got {n_slots}")
        self.n_slots = n_slots
        self._free = list(range(n_slots - 1, -1, -1))   # lowest slot first
        self._live: set = set()
        self._used: set = set()
        self.allocs = 0                                  # lifetime counter
        self.reuses = 0                # allocs that recycled a retired slot

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def live(self) -> frozenset:
        return frozenset(self._live)

    def alloc(self) -> int:
        if not self._free:
            raise PoolError("SlotPool.alloc on a full pool")
        s = self._free.pop()
        self._live.add(s)
        if s in self._used:
            self.reuses += 1
        self._used.add(s)
        self.allocs += 1
        return s

    def release(self, slot: int):
        if slot not in self._live:
            raise PoolError(f"SlotPool.release of non-live slot {slot}")
        self._live.remove(slot)
        self._free.append(slot)


class GenerationEngine:
    """Batched serving driver over a jitted ``Model.generate``.

    Requests are sorted by prompt length and grouped into batches of
    ``max_batch``; each batch is right-padded to a power-of-two prompt
    bucket and generated in one device program with per-row ``prompt_lens``
    (the model's internal position bookkeeping handles the ragged rows and
    any frontend prefix). Compiled executables are cached per shape.

    ``params`` may be a plain pytree OR core.bucketing.BucketedParams — a
    Collage-trained bucketed checkpoint serves directly, no fp32
    materialization (the leaf views materialize inside the jitted program).
    """

    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 sampling: Optional[SamplingParams] = None,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None, pad_id: Optional[int] = None,
                 eos_id: Optional[int] = None, pad_batches: bool = True,
                 seed: Optional[int] = None):
        # eos_id == pad_id etc. validate in SamplingParams.__post_init__;
        # the loose kwargs are a deprecation shim (None = not passed)
        sp = SamplingParams.resolve(sampling, dict(
            temperature=temperature, top_k=top_k, pad_id=pad_id,
            eos_id=eos_id, seed=seed))
        self.sampling = sp
        self.model = model
        self.params = params
        self.seed = sp.seed
        self._calls = 0            # advances the default sampling stream
        self.max_batch = max_batch
        # read-only: sampling config is baked into the cached traces
        self._temperature = float(sp.temperature)
        self._top_k = int(sp.top_k)
        self.pad_id = sp.pad_id
        self.eos_id = sp.eos_id
        # pad residual groups (B < max_batch) with dummy rows so every call
        # shares the (max_batch, bucket) shape — one compile per
        # (bucket, gen), not one per distinct residual size
        self.pad_batches = pad_batches
        self._exact_lens = model._has_recurrent_state()
        self._needs_frontend = (model.cfg.family == "vlm"
                                or model.cfg.is_encdec)
        self._fns: dict = {}
        # tokens_generated = real (pre-EOS / in-budget) tokens on real rows;
        # tokens_padded = scan slots burned on finished/dummy rows — the
        # goodput split continuous batching exists to fix
        self.stats = {"batches": 0, "tokens_generated": 0,
                      "tokens_padded": 0, "traces": 0}

    @property
    def temperature(self) -> float:
        """Sampling config is trace-baked: build a new engine to change it
        (mutating an attribute would silently not affect cached traces)."""
        return self._temperature

    @property
    def top_k(self) -> int:
        return self._top_k

    def _fn(self, max_new: int):
        fn = self._fns.get(max_new)
        if fn is None:
            def counted(params, batch, key, prompt_lens=None, gen_lens=None,
                        *, _n=max_new):
                self.stats["traces"] += 1    # Python side effect: runs only
                #                              when jit actually re-traces
                return self.model.generate(
                    params, batch, _n, key=key,
                    temperature=self._temperature, top_k=self._top_k,
                    prompt_lens=prompt_lens, gen_lens=gen_lens,
                    eos_id=self.eos_id, pad_id=self.pad_id)
            fn = jax.jit(counted)
            self._fns[max_new] = fn
        return fn

    @property
    def compile_count(self) -> int:
        """Traced program count — one per (gen length × batch ×
        prompt-bucket × raggedness) shape; the health signal that request
        bucketing is bounding compiles under arbitrary traffic."""
        return self.stats["traces"]

    def _group(self, order: Sequence[int], reqs: Sequence[Request]):
        """Batches of ≤ max_batch indices sharing a prompt bucket."""
        groups, cur, cur_bucket = [], [], None
        for i in order:
            n = len(reqs[i].tokens)
            b = n if self._exact_lens else _bucket_len(n)
            if cur and (b != cur_bucket or len(cur) == self.max_batch):
                groups.append((cur_bucket, cur))
                cur = []
            if not cur:
                cur_bucket = b
            cur.append(i)
        if cur:
            groups.append((cur_bucket, cur))
        return groups

    def generate(self, requests: Sequence[Request], max_new_tokens: int,
                 key=None) -> list[np.ndarray]:
        """Serve a list of ragged requests; returns per-request generated
        token arrays (max_new_tokens,), in the input order.

        Without an explicit ``key`` the sampling stream advances per call
        (folding a call counter into the engine seed), so repeated traffic
        gets fresh noise; pass a key to reproduce a specific batch."""
        if key is None:
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                     self._calls)
        self._calls += 1
        for i, r in enumerate(requests):
            if self._needs_frontend and r.frontend is None:
                raise ValueError(
                    f"request {i}: {self.model.cfg.name} requires frontend "
                    "embeddings on every request")
            if not self._needs_frontend and r.frontend is not None:
                raise ValueError(
                    f"request {i}: frontend given for a text-only arch")
        order = sorted(range(len(requests)),
                       key=lambda i: len(requests[i].tokens))
        budgets = [min(r.max_new_tokens or max_new_tokens, max_new_tokens)
                   for r in requests]
        # per-request budgets / EOS engage the masked scan; otherwise the
        # legacy un-masked trace is reused bit-identically
        masked = (self.eos_id is not None
                  or any(b != max_new_tokens for b in budgets))
        out: list = [None] * len(requests)
        pending = []
        for gi, (bucket, idxs) in enumerate(self._group(order, requests)):
            B = len(idxs)
            Bp = self.max_batch if self.pad_batches else B
            toks = np.full((Bp, bucket), self.pad_id, np.int32)
            lens = np.full((Bp,), bucket, np.int32)   # dummy rows full-length
            buds = np.ones((Bp,), np.int32)           # dummy rows: 1 token
            for r, i in enumerate(idxs):
                t = np.asarray(requests[i].tokens, np.int32)
                toks[r, :len(t)] = t
                lens[r] = len(t)
                buds[r] = budgets[i]
            batch = {"tokens": jnp.asarray(toks)}
            if self._needs_frontend:
                fes = [jnp.asarray(requests[i].frontend) for i in idxs]
                fes += [jnp.zeros_like(fes[0])] * (Bp - B)
                batch["frontend"] = jnp.stack(fes)
            ragged = None if (lens == bucket).all() else jnp.asarray(lens)
            gen, _ = self._fn(max_new_tokens)(
                self.params, batch, key=jax.random.fold_in(key, gi),
                prompt_lens=ragged,
                gen_lens=jnp.asarray(buds) if masked else None)
            pending.append((idxs, Bp, gen))  # host-sync AFTER all groups are
            #                               dispatched — keeps XLA's async
            #                               dispatch pipelining the groups
            self.stats["batches"] += 1
        for idxs, Bp, gen in pending:
            gen = np.asarray(gen)
            real = 0
            for r, i in enumerate(idxs):
                out[i] = gen[r]
                real += self._real_len(gen[r], budgets[i])
            self.stats["tokens_generated"] += real
            self.stats["tokens_padded"] += Bp * max_new_tokens - real
        return out

    def _real_len(self, row: np.ndarray, budget: int) -> int:
        """User-visible token count of an output row: up to and including
        the first EOS, capped by the request's budget."""
        if self.eos_id is not None:
            hits = np.flatnonzero(row[:budget] == self.eos_id)
            if hits.size:
                return int(hits[0]) + 1
        return int(budget)

    @property
    def goodput(self) -> float:
        """Real generated tokens / generation scan slots computed — the
        padding fraction is what continuous batching recycles."""
        total = self.stats["tokens_generated"] + self.stats["tokens_padded"]
        return self.stats["tokens_generated"] / max(total, 1)

    def run(self, requests: Sequence[Request], max_new_tokens: int,
            key=None) -> tuple[list[RequestResult], dict]:
        """Unified surface: the same (results, report) contract as
        ``ContinuousEngine.run``. The closed-batch engine admits everything
        immediately, so ``delay_ticks`` is always 0; malformed requests
        surface as ``finish_reason='error'`` rather than raising."""
        results: list[Optional[RequestResult]] = [None] * len(requests)
        good, idxmap = [], []
        for i, r in enumerate(requests):
            err = self._request_error(i, r)
            if err is not None:
                results[i] = RequestResult(np.zeros(0, np.int32), 0,
                                           "error", error=err)
            else:
                good.append(r)
                idxmap.append(i)
        outs = self.generate(good, max_new_tokens, key=key) if good else []
        for j, i in enumerate(idxmap):
            b = min(good[j].max_new_tokens or max_new_tokens,
                    max_new_tokens)
            nreal = self._real_len(outs[j], b)
            toks = np.asarray(outs[j][:nreal], np.int32)
            eos = (self.eos_id is not None and nreal > 0
                   and int(toks[-1]) == self.eos_id)
            results[i] = RequestResult(toks, nreal,
                                       "eos" if eos else "budget")
        report = {"mode": "closed", "goodput": self.goodput, **self.stats}
        return results, report

    def _request_error(self, i: int, r: Request) -> Optional[str]:
        if self._needs_frontend and r.frontend is None:
            return (f"request {i}: {self.model.cfg.name} requires frontend "
                    f"embeddings on every request")
        if not self._needs_frontend and r.frontend is not None:
            return f"request {i}: frontend given for a text-only arch"
        return None


class ContinuousEngine:
    """In-flight continuous batching over a slot-pool KV arena.

    The device side is two fixed-shape jitted programs — ``prefill_into``
    (one executable per prompt bucket, new rows scattered into free slots)
    and ``decode_segment`` (exactly one executable, advances ALL slots
    ``seg_len`` steps) — so compiles are bounded by the bucket grid no
    matter how requests churn. The host side is this scheduler:

      1. arrivals (virtual clock, ``Request.arrival`` ticks) join a FIFO
      2. admission: the queue head is admitted while a slot is free AND
         ``reserved + (F + bucket + budget) <= token_budget`` — strict FIFO
         so admission control never starves a long request
      3. admitted requests are grouped per prompt bucket into prefill
         launches of a FIXED batch (padded with dummy rows whose
         ``slot_idx = max_slots`` scatters are dropped out-of-bounds)
      4. one decode segment advances the pool; finished rows (EOS /
         budget) are retired BETWEEN segments, their slots released and
         refilled by step 2 on the next loop — no recompile

    The virtual clock charges ``seg_len`` ticks per decode segment (one
    tick ≡ one decode step) and ``ceil(bucket / seg_len)`` per prefill
    launch (prefill is token-parallel, so a whole bucket costs about one
    segment's wall time); queueing-delay percentiles in the report use
    this clock, keeping the benchmark gate hardware-independent.

    Outputs stream: ``on_token(req_idx, token)`` fires per real decoded
    token, ``on_complete(req_idx, tokens)`` when a row retires.
    """

    def __init__(self, model: Model, params, *, cache_len: int,
                 max_slots: int = 8, seg_len: int = 8,
                 prefill_batch: int = 2, token_budget: Optional[int] = None,
                 sampling: Optional[SamplingParams] = None,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 pad_id: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 seed: Optional[int] = None,
                 draft_model: Optional[Model] = None, draft_params=None,
                 spec_k: int = 0):
        if max_slots <= 0 or seg_len <= 0 or prefill_batch <= 0:
            raise AdmissionError(
                "max_slots, seg_len, prefill_batch must be > 0")
        sp = SamplingParams.resolve(sampling, dict(
            temperature=temperature, top_k=top_k, pad_id=pad_id,
            eos_id=eos_id, seed=seed))
        self.sampling = sp
        self.model = model
        self.params = params
        self.cache_len = int(cache_len)
        self.max_slots = int(max_slots)
        self.seg_len = int(seg_len)
        self.prefill_batch = int(prefill_batch)
        # admission reservation cap: Σ_live (frontend + bucket + budget)
        self.token_budget = (int(token_budget) if token_budget is not None
                             else self.max_slots * self.cache_len)
        self._temperature = float(sp.temperature)
        self._top_k = int(sp.top_k)
        self.pad_id = sp.pad_id
        self.eos_id = sp.eos_id
        self.seed = sp.seed
        self._calls = 0
        self._exact_lens = model._has_recurrent_state()
        self._needs_frontend = (model.cfg.family == "vlm"
                                or model.cfg.is_encdec)
        # speculative decoding: a draft model proposes spec_k tokens per
        # live slot, one target verify forward commits/rolls back (§11)
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        self.draft_params = draft_params
        if self.spec_k < 0:
            raise AdmissionError(f"spec_k must be >= 0, got {spec_k}")
        if self.spec_k:
            if draft_model is None or draft_params is None:
                raise AdmissionError(
                    f"spec_k={spec_k} requires draft_model= and "
                    f"draft_params=")
            if self._temperature > 0 or self._top_k > 0:
                raise CapabilityError(
                    "speculative decoding is greedy-only: under argmax the "
                    "k-token rejection guarantee degenerates to exact "
                    "prefix match (bit-parity); sampling acceptance is not "
                    "implemented — use spec_k=0 with temperature > 0")
            if model._has_recurrent_state():
                raise CapabilityError(
                    f"{model.cfg.name}: speculative decoding needs "
                    f"structural KV rollback by position; recurrent state "
                    f"(SSM/RWKV) cannot roll back a rejected suffix — use "
                    f"spec_k=0")
            if draft_model._has_recurrent_state():
                raise CapabilityError(
                    f"draft {draft_model.cfg.name}: recurrent draft state "
                    f"cannot roll back rejected proposals — use an "
                    f"attention draft")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise AdmissionError(
                    f"draft vocab {draft_model.cfg.vocab_size} != target "
                    f"vocab {model.cfg.vocab_size}")
        self._prefills: dict = {}
        self._draft_prefills: dict = {}
        self._seg = None
        self._draft = None
        self._verify = None
        self.stats = {"prefill_launches": 0, "segments": 0,
                      "prefill_slot_rows": 0, "decode_slot_steps": 0,
                      "tokens_real": 0, "slot_allocs": 0, "max_reserved": 0,
                      "prefill_traces": 0, "decode_traces": 0,
                      "verify_launches": 0, "target_slot_forwards": 0,
                      "spec_tokens_committed": 0, "draft_traces": 0,
                      "verify_traces": 0, "draft_prefill_traces": 0}

    # ------------------------------------------------------ jitted seams --
    def _prefill_fn(self, bucket: int):
        fn = self._prefills.get(bucket)
        if fn is None:
            def counted(params, slots, batch, slot_idx, budget, key,
                        prompt_lens=None):
                self.stats["prefill_traces"] += 1
                return self.model.prefill_into(
                    params, slots, batch, slot_idx, budget, key,
                    cache_len=self.cache_len, prompt_lens=prompt_lens,
                    temperature=self._temperature, top_k=self._top_k,
                    eos_id=self.eos_id)
            fn = jax.jit(counted, donate_argnums=(1,))
            self._prefills[bucket] = fn
        return fn

    def _seg_fn(self):
        if self._seg is None:
            def counted(params, slots, key):
                self.stats["decode_traces"] += 1
                return self.model.decode_segment(
                    params, slots, key, seg_len=self.seg_len,
                    temperature=self._temperature, top_k=self._top_k,
                    eos_id=self.eos_id, pad_id=self.pad_id)
            self._seg = jax.jit(counted, donate_argnums=(1,))
        return self._seg

    def _draft_prefill_fn(self, bucket: int):
        """Mirror the target prefill into the draft cache pool — one
        executable per prompt bucket, like the target's."""
        fn = self._draft_prefills.get(bucket)
        if fn is None:
            def counted(dparams, draft, batch, slot_idx, prompt_lens=None):
                self.stats["draft_prefill_traces"] += 1
                return self.draft_model.prefill_state_into(
                    dparams, draft, batch, slot_idx,
                    cache_len=self.cache_len, prompt_lens=prompt_lens)
            fn = jax.jit(counted, donate_argnums=(1,))
            self._draft_prefills[bucket] = fn
        return fn

    def _draft_fn(self):
        """ONE draft-propose executable: a fixed-shape greedy scan over the
        draft pool, driven by the TARGET's authoritative tok/pos/run."""
        if self._draft is None:
            def counted(dparams, draft, tok, pos, active, done):
                self.stats["draft_traces"] += 1
                return self.draft_model.draft_propose(
                    dparams, draft, tok, pos, active & ~done,
                    spec_k=self.spec_k)
            self._draft = jax.jit(counted, donate_argnums=(1,))
        return self._draft

    def _verify_fn(self):
        """ONE verify executable: a single batched (max_slots, spec_k + 1)
        target forward commits accepted prefixes and rolls back the rest."""
        if self._verify is None:
            def counted(params, slots, props):
                self.stats["verify_traces"] += 1
                return self.model.spec_verify(
                    params, slots, props, eos_id=self.eos_id,
                    pad_id=self.pad_id)
            self._verify = jax.jit(counted, donate_argnums=(1,))
        return self._verify

    @property
    def compile_count(self) -> int:
        return (self.stats["prefill_traces"] + self.stats["decode_traces"]
                + self.stats["draft_prefill_traces"]
                + self.stats["draft_traces"] + self.stats["verify_traces"])

    def _bucket(self, n: int) -> int:
        return n if self._exact_lens else _bucket_len(n)

    def _reservation(self, i: int, r: Request, max_new_tokens: int) -> tuple:
        """Admission-time validation for one request; raises
        ``AdmissionError`` if it could never be scheduled. Returns
        (budget, reservation)."""
        if self._needs_frontend and r.frontend is None:
            raise AdmissionError(
                f"request {i}: frontend embeddings required")
        b = min(r.max_new_tokens or max_new_tokens, max_new_tokens)
        res = self.model._prefix_len + self._bucket(len(r.tokens)) + b
        if res > self.cache_len:
            raise AdmissionError(
                f"request {i}: frontend {self.model._prefix_len} + prompt "
                f"bucket {self._bucket(len(r.tokens))} + budget {b} = "
                f"{res} exceeds cache_len {self.cache_len}")
        if res > self.token_budget:
            raise AdmissionError(
                f"request {i}: reservation {res} exceeds token_budget "
                f"{self.token_budget} — it could never be admitted")
        return b, res

    # -------------------------------------------------------- the server --
    def serve(self, requests: Sequence[Request], max_new_tokens: int, *,
              key=None, on_token: Optional[Callable[[int, int], None]] = None,
              on_complete: Optional[Callable[[int, np.ndarray], None]] = None):
        """Run an open-stream trace to completion.

        Returns ``(outputs, report)``: per-request np arrays of REAL
        generated tokens (variable length — up to and including EOS, capped
        by the request budget), in input order, plus a report dict with
        goodput, virtual-clock queueing-delay percentiles, and the
        structural counters the serving benchmark gates on."""
        if key is None:
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                     self._calls)
        self._calls += 1
        n = len(requests)
        budgets, resv = [], []
        for i, r in enumerate(requests):
            b, res = self._reservation(i, r, max_new_tokens)
            budgets.append(b)
            resv.append(res)

        pool = SlotPool(self.max_slots)
        draft = None
        if self.spec_k:
            spec = self.model.init_spec_state(
                self.draft_model, self.max_slots, self.cache_len)
            slots, draft = spec.slots, spec.draft
        else:
            slots = self.model.init_slot_state(self.max_slots,
                                               self.cache_len)
        arr_order = sorted(range(n), key=lambda i: (requests[i].arrival, i))
        arrived: deque = deque()
        p = 0                       # next not-yet-arrived index in arr_order
        clock = 0.0
        reserved = 0
        ev = 0                      # key-fold event counter
        slot_req: dict[int, int] = {}
        slot_ngen = np.zeros(self.max_slots, np.int64)  # host n_gen mirror
        outputs: list[list[int]] = [[] for _ in range(n)]
        delays = np.zeros(n)
        done_tick = np.zeros(n)
        completed = 0

        def retire(s: int, i: int):
            nonlocal reserved, completed
            pool.release(s)
            del slot_req[s]
            reserved -= resv[i]
            done_tick[i] = clock
            completed += 1
            if on_complete is not None:
                on_complete(i, np.asarray(outputs[i], np.int32))

        def emit(i: int, t: int):
            outputs[i].append(t)
            self.stats["tokens_real"] += 1
            if on_token is not None:
                on_token(i, t)

        while completed < n:
            while p < n and requests[arr_order[p]].arrival <= clock + 1e-9:
                arrived.append(arr_order[p])
                p += 1
            # strict-FIFO admission under the slot + token-budget caps
            admits: list[int] = []
            while (arrived and pool.n_free > len(admits)
                   and reserved + sum(resv[j] for j in admits)
                   + resv[arrived[0]] <= self.token_budget):
                admits.append(arrived.popleft())
            # group same-bucket admits into fixed-shape prefill launches
            g = 0
            while g < len(admits):
                bucket = self._bucket(len(requests[admits[g]].tokens))
                group = [admits[g]]
                g += 1
                while (g < len(admits) and len(group) < self.prefill_batch
                       and self._bucket(len(requests[admits[g]].tokens))
                       == bucket):
                    group.append(admits[g])
                    g += 1
                Bp = self.prefill_batch
                toks = np.full((Bp, bucket), self.pad_id, np.int32)
                lens = np.full((Bp,), bucket, np.int32)
                sidx = np.full((Bp,), self.max_slots, np.int32)  # dummy→drop
                buds = np.ones((Bp,), np.int32)
                for r, i in enumerate(group):
                    t = np.asarray(requests[i].tokens, np.int32)
                    toks[r, :len(t)] = t
                    lens[r] = len(t)
                    s = pool.alloc()
                    slot_req[s] = i
                    sidx[r] = s
                    buds[r] = budgets[i]
                    reserved += resv[i]
                    delays[i] = clock - requests[i].arrival
                self.stats["max_reserved"] = max(self.stats["max_reserved"],
                                                 reserved)
                batch = {"tokens": jnp.asarray(toks)}
                if self._needs_frontend:
                    fes = [jnp.asarray(requests[i].frontend) for i in group]
                    fes += [jnp.zeros_like(fes[0])] * (Bp - len(group))
                    batch["frontend"] = jnp.stack(fes)
                # attention archs ALWAYS pass prompt_lens (one trace per
                # bucket, ragged or not); recurrent archs bucket by exact
                # length, so rows are never ragged and prompt_lens stays None
                pl = None if self._exact_lens else jnp.asarray(lens)
                tok0, slots = self._prefill_fn(bucket)(
                    self.params, slots, batch, jnp.asarray(sidx),
                    jnp.asarray(buds), jax.random.fold_in(key, ev),
                    prompt_lens=pl)
                if self.spec_k:
                    # mirror the rows into the draft cache pool — the
                    # draft launch overlaps the (much larger) target
                    # prefill, so the virtual clock charges nothing extra
                    draft = self._draft_prefill_fn(bucket)(
                        self.draft_params, draft, batch,
                        jnp.asarray(sidx), prompt_lens=pl)
                ev += 1
                clock += max(1, math.ceil(bucket / self.seg_len))
                self.stats["prefill_launches"] += 1
                self.stats["prefill_slot_rows"] += Bp
                tok0 = np.asarray(tok0)
                for r, i in enumerate(group):
                    t0 = int(tok0[r])
                    emit(i, t0)
                    slot_ngen[sidx[r]] = 1
                    # instantly-done rows (budget 1, or first token is EOS)
                    # retire before ever occupying a decode segment
                    if budgets[i] <= 1 or (self.eos_id is not None
                                           and t0 == self.eos_id):
                        retire(int(sidx[r]), i)
            if slot_req:
                if self.spec_k:
                    # speculative round: draft proposes spec_k per live
                    # slot, ONE target verify forward commits 1..k+1
                    # tokens per slot for ~1 virtual-clock tick
                    props, draft = self._draft_fn()(
                        self.draft_params, draft, slots.tok,
                        slots.state.pos, slots.active, slots.done)
                    emitted, slots = self._verify_fn()(
                        self.params, slots, props)
                    clock += 1
                    self.stats["verify_launches"] += 1
                    # every slot still in slot_req is running (done rows
                    # retire the moment they're read back)
                    self.stats["target_slot_forwards"] += len(slot_req)
                    self.stats["decode_slot_steps"] += \
                        self.max_slots * (self.spec_k + 1)
                else:
                    emitted, slots = self._seg_fn()(
                        self.params, slots, jax.random.fold_in(key, ev))
                    ev += 1
                    clock += self.seg_len
                    self.stats["segments"] += 1
                    self.stats["decode_slot_steps"] += \
                        self.max_slots * self.seg_len
                em = np.asarray(emitted)
                ngen = np.asarray(slots.n_gen)
                done = np.asarray(slots.done)
                for s, i in list(slot_req.items()):
                    k = int(ngen[s] - slot_ngen[s])   # done is monotone in a
                    for t in em[s, :k]:               # segment → real tokens
                        emit(i, int(t))               # are a prefix
                    if self.spec_k:
                        self.stats["spec_tokens_committed"] += k
                    slot_ngen[s] = ngen[s]
                    if done[s]:
                        retire(s, i)
            elif not arrived:
                if p >= n:          # nothing live, queued, or future: bug
                    raise PoolError(
                        "scheduler stalled with requests outstanding")
                clock = max(clock, requests[arr_order[p]].arrival)  # idle jump
            else:
                # arrived-but-unadmitted with an EMPTY pool is impossible:
                # reserved == 0 and every reservation was validated above
                raise PoolError("admission stalled with free slots")

        self.stats["slot_allocs"] = pool.allocs
        token_slots = (self.stats["prefill_slot_rows"]
                       + self.stats["decode_slot_steps"])
        report = {
            "requests": n,
            "max_slots": self.max_slots,
            "seg_len": self.seg_len,
            "prefill_batch": self.prefill_batch,
            "token_budget": self.token_budget,
            "clock_ticks": float(clock),
            "tokens_real": self.stats["tokens_real"],
            "token_slots": token_slots,
            "goodput": self.stats["tokens_real"] / max(token_slots, 1),
            "delay_p50": float(np.percentile(delays, 50)),
            "delay_p99": float(np.percentile(delays, 99)),
            "completion_p99": float(np.percentile(
                done_tick - np.array([r.arrival for r in requests]), 99)),
            "prefill_launches": self.stats["prefill_launches"],
            "segments": self.stats["segments"],
            "slot_allocs": pool.allocs,
            "slot_reuse": pool.reuses,
            "max_reserved": self.stats["max_reserved"],
            "prefill_traces": self.stats["prefill_traces"],
            "decode_traces": self.stats["decode_traces"],
            "delays": [float(d) for d in delays],
        }
        if self.spec_k:
            fw = self.stats["target_slot_forwards"]
            committed = self.stats["spec_tokens_committed"]
            report.update({
                "spec_k": self.spec_k,
                "verify_launches": self.stats["verify_launches"],
                "target_slot_forwards": fw,
                "spec_tokens_committed": committed,
                # each verify forward commits 1 token for free (the bonus
                # token) plus 0..k accepted proposals — this is the
                # fraction of proposal slots that landed
                "acceptance_rate": (committed - fw) / max(fw * self.spec_k,
                                                          1),
                "draft_traces": self.stats["draft_traces"],
                "verify_traces": self.stats["verify_traces"],
                "draft_prefill_traces": self.stats["draft_prefill_traces"],
            })
        return [np.asarray(o, np.int32) for o in outputs], report

    def run(self, requests: Sequence[Request], max_new_tokens: int, *,
            key=None) -> tuple[list[RequestResult], dict]:
        """Unified surface over ``serve``: inadmissible requests come back
        as ``finish_reason='error'`` (with the admission message) instead
        of failing the whole trace; admissible ones carry their
        virtual-clock queueing delay."""
        results: list[Optional[RequestResult]] = [None] * len(requests)
        good, idxmap = [], []
        for i, r in enumerate(requests):
            try:
                self._reservation(i, r, max_new_tokens)
            except AdmissionError as e:
                results[i] = RequestResult(np.zeros(0, np.int32), 0,
                                           "error", error=str(e))
            else:
                good.append(r)
                idxmap.append(i)
        if good:
            outs, report = self.serve(good, max_new_tokens, key=key)
        else:
            outs, report = [], {"requests": 0}
        for j, i in enumerate(idxmap):
            toks = outs[j]
            eos = (self.eos_id is not None and len(toks) > 0
                   and int(toks[-1]) == self.eos_id)
            results[i] = RequestResult(
                toks, int(len(toks)), "eos" if eos else "budget",
                delay_ticks=float(report["delays"][j]))
        return results, report


def draft_from_target(model: Model, params, spec: str):
    """Build a (draft_model, draft_params) pair from the target itself.

    ``"self"`` — the target doubles as its own draft (acceptance == 1.0:
    useful for parity/boundary tests, not for speed). ``"layers:N"`` — a
    depth-N truncation sharing the target's embed/head and its FIRST N
    stacked layer groups (no retraining, correlated predictions → nonzero
    acceptance on the seeded benchmark trace). Truncation needs a
    single-group decoder (the dense families); pass an explicit draft for
    mixed-program archs."""
    if spec == "self":
        return model, params
    if not spec.startswith("layers:"):
        raise AdmissionError(
            f"unknown draft spec {spec!r} (self | layers:N)")
    n = int(spec.split(":", 1)[1])
    cfg = model.cfg
    if n <= 0 or n >= cfg.n_layers:
        raise AdmissionError(
            f"layers:{n} draft needs 0 < N < n_layers={cfg.n_layers}")
    if len(cfg.decoder_program()) != 1:
        raise CapabilityError(
            f"{cfg.name}: layers:N draft slicing needs a single-group "
            f"decoder program; pass an explicit draft model")
    dcfg = dataclasses.replace(cfg, n_layers=n)
    tree = params.tree() if hasattr(params, "tree") else params
    dparams = dict(tree)
    dparams["decoder"] = {
        "groups": [jax.tree_util.tree_map(lambda x: x[:n],
                                          tree["decoder"]["groups"][0])],
        "final_norm": tree["decoder"]["final_norm"],
    }
    return build_model(dcfg), dparams


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-tiny")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of ragged requests to simulate")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine max batch size")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max simulated prompt length")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="treat this token id as EOS (early exit)")
    ap.add_argument("--continuous", action="store_true",
                    help="serve an open Poisson stream through the "
                         "slot-pool ContinuousEngine instead of the "
                         "closed-batch GenerationEngine")
    ap.add_argument("--slots", type=int, default=8,
                    help="continuous: slot-pool arena size")
    ap.add_argument("--seg-len", type=int, default=8,
                    help="continuous: decode steps per jitted segment")
    ap.add_argument("--prefill-batch", type=int, default=2,
                    help="continuous: fixed prefill launch batch")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="continuous: Poisson arrivals per virtual tick")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="continuous: admission cap on reserved tokens")
    ap.add_argument("--speculative-draft", default=None,
                    help="continuous: enable speculative decoding with a "
                         "draft built from the target — 'self' (target as "
                         "its own draft; parity testing) or 'layers:N' "
                         "(depth-N truncation sharing embed/head); greedy "
                         "only, output is bit-identical to non-speculative")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative: draft proposals per slot per verify "
                         "round (the verify forward is (slots, k+1) wide)")
    ap.add_argument("--flash-min-len", type=int, default=None,
                    help="prefill dispatches causal self-attention to the "
                         "Pallas flash kernels when prompt_len >= this "
                         "(0 = off, unset = config default) — long-prompt "
                         "prefill without the O(L^2) score buffer")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the decoder to this many layers (a whole "
                         "number of layer periods); widths stay published")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.configure()

    cfg = with_layers(get_config(args.arch, smoke=args.smoke), args.layers)
    if args.flash_min_len is not None:
        cfg = dataclasses.replace(cfg, flash_min_len=args.flash_min_len)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    corpus = SyntheticCorpus(cfg.vocab_size, args.prompt_len,
                             max(args.requests, 1), seed=args.seed)
    toks = np.asarray(corpus.batch_at(0)["tokens"])
    fe_all = None
    if cfg.is_encdec or cfg.family == "vlm":
        fe_all = np.asarray(corpus.frontend_at(
            0, cfg.d_model, cfg.frontend_len, jnp.dtype(cfg.dtype)))
    rng = np.random.default_rng(args.seed)
    lo = max(args.prompt_len // 2, 1)
    requests = []
    arrival = 0.0
    for i in range(args.requests):
        n = int(rng.integers(lo, args.prompt_len + 1))
        if model._has_recurrent_state():
            n = args.prompt_len          # exact-length batching demo
        fe = None if fe_all is None else fe_all[i]
        gen_i = None
        if args.continuous:              # mixed per-request gen lengths —
            gen_i = int(rng.integers(1, args.gen + 1))   # the churn driver
            arrival += float(rng.exponential(1.0 / max(args.arrival_rate,
                                                       1e-9)))
        requests.append(Request(tokens=toks[i, :n], frontend=fe,
                                max_new_tokens=gen_i, arrival=arrival))

    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, eos_id=args.eos_id,
                              seed=args.seed)
    if args.continuous:
        cache_len = _bucket_len(args.prompt_len) + args.gen + \
            (cfg.frontend_len if (cfg.is_encdec or cfg.family == "vlm")
             else 0)
        spec_kw: dict = {}
        mode = "continuous"
        if args.speculative_draft:
            dm, dp = draft_from_target(model, params, args.speculative_draft)
            spec_kw = dict(draft_model=dm, draft_params=dp,
                           spec_k=args.spec_k)
            mode = "speculative"
        engine = make_engine(
            model, params, mode=mode, sampling=sampling,
            cache_len=cache_len, max_slots=args.slots,
            seg_len=args.seg_len, prefill_batch=args.prefill_batch,
            token_budget=args.token_budget, **spec_kw)
        t0 = time.time()
        outs, report = engine.serve(requests, args.gen,
                                    key=jax.random.PRNGKey(args.seed + 1))
        t_serve = time.time() - t0
        print(f"{mode}: {args.requests} requests, {args.slots} slots, "
              f"seg_len {args.seg_len}, token_budget {engine.token_budget}")
        print(f"  wall (incl. {engine.compile_count} compiles): "
              f"{t_serve*1e3:.1f} ms")
        print(f"  goodput {report['goodput']:.3f} "
              f"({report['tokens_real']} real / {report['token_slots']} "
              f"token-slots), slot reuse {report['slot_reuse']}")
        print(f"  queueing delay (virtual ticks): "
              f"p50 {report['delay_p50']:.1f}  p99 {report['delay_p99']:.1f}")
        if engine.spec_k:
            print(f"  speculative: k={report['spec_k']}, acceptance "
                  f"{report['acceptance_rate']:.3f}, "
                  f"{report['target_slot_forwards']} target forwards for "
                  f"{report['spec_tokens_committed']} committed tokens")
        print("sample generations (token ids):")
        for o in outs[:2]:
            print("  ", [int(t) for t in o[:16]])
        return outs

    engine = make_engine(model, params, mode="closed", sampling=sampling,
                         max_batch=args.batch)
    t0 = time.time()
    outs = engine.generate(requests, args.gen,
                           key=jax.random.PRNGKey(args.seed + 1))
    t_warm = time.time() - t0
    t0 = time.time()
    outs = engine.generate(requests, args.gen,
                           key=jax.random.PRNGKey(args.seed + 1))
    t_serve = time.time() - t0
    n_tok = args.requests * args.gen
    print(f"engine: {args.requests} requests (ragged prompts ≤ "
          f"{args.prompt_len}) × {args.gen} new tokens")
    print(f"  warmup (incl. {engine.compile_count} compiles): "
          f"{t_warm*1e3:.1f} ms")
    print(f"  steady-state: {t_serve*1e3:.1f} ms "
          f"({n_tok / max(t_serve, 1e-9):.1f} tok/s)")
    print(f"  tokens: {engine.stats['tokens_generated']} generated, "
          f"{engine.stats['tokens_padded']} padded "
          f"(goodput {engine.goodput:.3f})")
    print("sample generations (token ids):")
    for o in outs[:2]:
        print("  ", [int(t) for t in o[:16]])
    return outs


if __name__ == "__main__":
    main()
