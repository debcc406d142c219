"""Top-level Model API: init / forward / loss / prefill / decode_step /
generate / input_specs — uniform across all 10 assigned architecture
families.

Batch dict conventions:
  train/prefill : {"tokens": (B, L) i32, "labels": (B, L) i32,
                   "frontend": (B, F, D) bf16 (vlm/audio only)}
  decode        : decode_step(params, state, token (B,1) i32)

Serving (DESIGN.md §6): the KV/recurrent caches travel inside a
``DecodeState`` that also carries the per-row cache position ``pos (B,)``.
Position bookkeeping is *internal* — ``prefill`` sets ``pos`` to the true
cache position (including the VLM patch-prefix length and per-row ragged
prompt lengths) and ``decode_step`` advances it, so callers never compute
positions and cannot reproduce the frontend-offset bug class. ``generate``
is the jit-resident decode loop (lax.scan over tokens, in-jit sampling)
that serving and benchmarks drive; it supports EOS / per-request token
budgets (finished rows freeze ``pos`` and emit ``pad_id``).

Continuous batching (DESIGN.md §10): ``SlotState`` generalizes the decode
arena to a fixed ``(max_slots, cache_len)`` slot pool with per-slot
liveness; ``prefill_into`` scatters freshly prefilled requests into free
slots and ``decode_segment`` advances the whole pool a fixed number of
steps — both are fixed-shape programs, so the host scheduler
(launch.serve.ContinuousEngine) retires/refills rows between segments
without ever recompiling.

``[audio]``/``[vlm]`` frontends are STUBS per the task spec: ``input_specs``
provides precomputed frame/patch embeddings; the backbone is real.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tf
from repro.models.layers import ACC, embed_init, embed_lookup, rms_norm, rms_norm_init
from repro.train import scopes

PyTree = Any

# MoE load-balance penalty weight in the training objective. The single
# definition: Model.loss AND the pipelined loss (train/sharded.py) both
# combine `ce + AUX_LOSS_COEF · aux` from here, so the two paths cannot
# silently desynchronize.
AUX_LOSS_COEF = 0.01


def _as_tree(params):
    """Materialize leaf views from BucketedParams (core.bucketing) at the
    model-apply boundary; plain pytrees pass through. Duck-typed so serving
    a Collage-trained bucketed checkpoint needs no fp32 materialization."""
    return params.tree() if hasattr(params, "tree") else params


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class DecodeState:
    """Generation-loop carry: per-group caches + per-row cache position.

    ``pos[b]`` is the next cache write position of row b == the number of
    valid entries (frontend prefix + prompt + generated so far). It is the
    single source of truth for RoPE positions and attention masking."""

    layers: tuple                 # one cache pytree per decoder group
    pos: jax.Array                # (B,) int32

    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("layers"), self.layers),
                 (jax.tree_util.GetAttrKey("pos"), self.pos)), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children[0]), children[1])


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class SlotState:
    """Slot-pool serving carry (continuous batching, DESIGN.md §10).

    The KV arena is a ``DecodeState`` over a fixed ``max_slots`` batch; the
    per-slot vectors make row liveness part of the jitted carry so the host
    scheduler (launch.serve.ContinuousEngine) only ever *reads* them:

      tok    (B, 1) i32  — last sampled token, not yet consumed
      active (B,)  bool  — slot holds an admitted request (free slots False)
      done   (B,)  bool  — request finished (EOS / budget); stays True until
                           the slot is refilled by ``prefill_into``
      n_gen  (B,)  i32   — tokens emitted so far (including the prefill one)
      budget (B,)  i32   — per-request max_new_tokens

    A slot advances iff ``active & ~done``; retired rows freeze ``pos``,
    drop their KV write, and emit ``pad_id`` — so one fixed-shape
    ``decode_segment`` program serves an arbitrarily churning request mix."""

    state: DecodeState
    tok: jax.Array
    active: jax.Array
    done: jax.Array
    n_gen: jax.Array
    budget: jax.Array

    _FIELDS = ("state", "tok", "active", "done", "n_gen", "budget")

    def tree_flatten_with_keys(self):
        return (tuple((jax.tree_util.GetAttrKey(f), getattr(self, f))
                      for f in self._FIELDS), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def run(self):
        """(B,) bool — slots that advance this step."""
        return self.active & ~self.done


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class SpecState:
    """Speculative-decoding carry (DESIGN.md §11): the target's slot pool
    paired with the draft model's cache pool over the same slot grid.

    ``slots`` is authoritative for ALL bookkeeping (tok/active/done/
    n_gen/budget/pos); the draft half carries only its own caches + a pos
    vector that is OVERWRITTEN from the target's at every propose launch —
    after a rejection both pools roll back by index (stale rows beyond
    ``pos`` are never attended and are overwritten on re-advance), so the
    two stay consistent without any copy."""

    slots: SlotState              # target pool (authoritative)
    draft: DecodeState            # draft-model caches over the same grid

    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("slots"), self.slots),
                 (jax.tree_util.GetAttrKey("draft"), self.draft)), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def greedy_tokens(logits):
    """Tie-robust greedy selection: argmax over logits rounded to the
    model compute dtype (bf16). The fp32 logits of the SAME token stream
    differ in the last bits between kernel widths (a width-1 decode step
    and a width-(k+1) verify forward tile their GEMMs differently), so a
    raw fp32 argmax can flip on sub-bf16-ULP margins — which are compile
    -shape noise, not model preference, in a bf16-compute model. Rounding
    first collapses those margins to exact ties (argmax then breaks them
    by index, identically everywhere); a flip now needs the noise to push
    a logit across a bf16 boundary AND the top-2 gap under one ULP at
    once. Every greedy site (generate, prefill sampling, decode_segment,
    draft_propose, spec_verify) MUST route through here — speculative
    bit-parity with plain greedy decode depends on it."""
    return jnp.argmax(logits.astype(jnp.bfloat16), axis=-1).astype(
        jnp.int32)


def sample_logits(logits, key, temperature: float = 0.0, top_k: int = 0):
    """In-jit sampling: greedy / temperature / top-k. logits (B, V) fp32.
    ``temperature``/``top_k`` are static (they change the compiled program);
    the PRNG ``key`` is consumed exactly once per call."""
    if temperature <= 0.0:
        return greedy_tokens(logits)
    logits = logits.astype(ACC) / temperature
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------- params --
    def init(self, key) -> PyTree:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, 8)
        params = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)}
        params["decoder"] = {
            "groups": [tf.group_init(k, g, cfg, dtype)
                       for k, g in zip(jax.random.split(keys[1], 8),
                                       cfg.decoder_program())],
            "final_norm": rms_norm_init(cfg.d_model, dtype),
        }
        if cfg.is_encdec:
            params["encoder"] = {
                "groups": [tf.group_init(k, g, cfg, dtype)
                           for k, g in zip(jax.random.split(keys[2], 8),
                                           cfg.encoder_program())],
                "final_norm": rms_norm_init(cfg.d_model, dtype),
            }
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(keys[3], cfg.vocab_size,
                                           cfg.d_model, dtype).T
        return params

    # ------------------------------------------------------------ helpers --
    def _head(self, params, x):
        cfg = self.cfg
        with jax.named_scope(scopes.HEAD):
            x = rms_norm(x, params["decoder"]["final_norm"], cfg.norm_eps)
            w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
            return jnp.matmul(x, w, preferred_element_type=ACC)  # logits fp32

    def _encode(self, params, frontend):
        cfg = self.cfg
        x = frontend
        for g, gp in zip(cfg.encoder_program(), params["encoder"]["groups"]):
            x, _ = tf.group_apply(gp, x, g, cfg)
        return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    def _decoder_input(self, params, batch):
        """Token embeddings, with the VLM patch prefix concatenated."""
        cfg = self.cfg
        with jax.named_scope(scopes.EMBED):
            x = embed_lookup(params["embed"], batch["tokens"])
            if cfg.family == "vlm":
                x = jnp.concatenate([batch["frontend"].astype(x.dtype), x], axis=1)
            return x

    @property
    def _prefix_len(self) -> int:
        """Decoder-sequence prefix occupied by the frontend: VLM patches sit
        in the decoder cache; enc-dec frontends go through the encoder."""
        return self.cfg.frontend_len if self.cfg.family == "vlm" else 0

    # ------------------------------------------------------------ forward --
    def forward(self, params, batch, remat: str = "none"):
        """Full-sequence logits (training / prefill-style). Returns
        (logits, aux_loss)."""
        params = _as_tree(params)
        cfg = self.cfg
        memory = None
        if cfg.is_encdec:
            memory = self._encode(params, batch["frontend"].astype(
                jnp.dtype(cfg.dtype)))
        x = self._decoder_input(params, batch)
        aux = jnp.zeros((), ACC)
        for g, gp in zip(cfg.decoder_program(), params["decoder"]["groups"]):
            x, a = tf.group_apply(gp, x, g, cfg, memory=memory, remat=remat)
            aux = aux + a
        return self._head(params, x), aux

    @staticmethod
    def token_ce(logits, labels) -> jax.Array:
        """Next-token cross entropy (fp32) from full-sequence logits.

        Shapes (..., L, V) vs (..., L) — any leading batch/microbatch dims.
        The single definition of the training objective: ``loss`` and the
        sharded engine's pipelined loss (train/sharded.py) both call it, so
        masking/shift changes cannot silently diverge between paths."""
        with jax.named_scope(scopes.HEAD):
            logits = logits[..., :-1, :]
            targets = labels[..., 1:]
            mask = (targets >= 0).astype(ACC)
            logp = jax.nn.log_softmax(logits.astype(ACC), axis=-1)
            ll = jnp.take_along_axis(
                logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
            ntok = jnp.maximum(mask.sum(), 1.0)
            return -(ll * mask).sum() / ntok

    def loss(self, params, batch, remat: str = "none"):
        """Next-token cross entropy (fp32), MoE aux added; returns
        (loss, metrics_dict)."""
        cfg = self.cfg
        logits, aux = self.forward(params, batch, remat=remat)
        if cfg.family == "vlm":   # loss only on the text segment
            logits = logits[:, batch["frontend"].shape[1]:]
        ce = self.token_ce(logits, batch["labels"])
        total = ce + AUX_LOSS_COEF * aux
        return total, {"ce": ce, "aux": aux, "ppl": jnp.exp(ce)}

    # ------------------------------------------------------------ serving --
    def init_decode_state(self, batch_size: int, cache_len: int) -> DecodeState:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        mem_len = cfg.frontend_len if cfg.is_encdec else 0
        layers = tuple(tf.group_init_cache(g, cfg, batch_size, cache_len, dtype,
                                           memory_len=mem_len)
                       for g in cfg.decoder_program())
        return DecodeState(layers, jnp.zeros((batch_size,), jnp.int32))

    def _has_recurrent_state(self) -> bool:
        return any(s.kind in ("mamba", "rwkv_tmix", "rwkv_cmix")
                   for g in self.cfg.decoder_program() for s in g.period)

    def prefill(self, params, batch, cache_len: int,
                prompt_lens: Optional[jax.Array] = None):
        """Process the prompt; returns (per-row last-valid-position logits
        (B,1,V), DecodeState).

        ``prompt_lens (B,) i32``: valid prompt length per row for ragged
        batches (tokens right-padded to the common length). Recurrent-state
        archs (SSM/RWKV/hybrid) consume pad tokens into their state, so
        ragged prefill is only supported for pure-attention caches — batch
        those archs by exact length (the serve engine does)."""
        params = _as_tree(params)
        cfg = self.cfg
        B, T = batch["tokens"].shape
        F = self._prefix_len
        assert cache_len >= F + T, (
            f"cache_len {cache_len} < frontend {F} + prompt {T}: the KV "
            f"write would clip")
        if prompt_lens is not None and self._has_recurrent_state():
            raise ValueError(
                "ragged prefill (prompt_lens) unsupported for recurrent-state "
                "archs: pad tokens would pollute the carried state; batch by "
                "exact length instead")
        memory = None
        if cfg.is_encdec:
            memory = self._encode(params, batch["frontend"].astype(
                jnp.dtype(cfg.dtype)))
        x = self._decoder_input(params, batch)
        layers = []
        for g, gp in zip(cfg.decoder_program(), params["decoder"]["groups"]):
            x, c = tf.group_prefill(gp, x, g, cfg, cache_len, memory=memory)
            layers.append(c)
        if prompt_lens is None:
            pos = jnp.full((B,), F + T, jnp.int32)
        else:
            pos = F + prompt_lens.astype(jnp.int32)
        # last valid position per row, in decoder-sequence coordinates
        x_last = jnp.take_along_axis(x, (pos - 1)[:, None, None], axis=1)
        logits = self._head(params, x_last)
        return logits, DecodeState(tuple(layers), pos)

    def decode_step(self, params, state: DecodeState, token, active=None):
        """One-token serve step: token (B,1) i32; positions come from
        ``state.pos``. Returns (logits (B,1,V) fp32, new DecodeState).

        ``active (B,) bool``: slot-masked decode (continuous batching) —
        rows with False freeze ``pos``, keep their caches bit-identical
        (KV writes dropped, recurrent states re-selected) and their logits
        are garbage the caller must discard. None = all rows live, with
        the exact pre-slot-pool lowering."""
        params = _as_tree(params)
        cfg = self.cfg
        x = embed_lookup(params["embed"], token)
        new_layers = []
        for g, gp, c in zip(cfg.decoder_program(),
                            params["decoder"]["groups"], state.layers):
            x, nc = tf.group_decode(gp, x, g, cfg, c, state.pos,
                                    active=active)
            new_layers.append(nc)
        adv = 1 if active is None else active.astype(jnp.int32)
        return self._head(params, x), DecodeState(tuple(new_layers),
                                                  state.pos + adv)

    def decode_verify(self, params, state: DecodeState, tokens, active=None):
        """Verify-mode forward (speculative decoding): tokens (B, W) i32 is
        the current token + the draft's W-1 proposals. ONE batched forward
        returns per-position logits (B, W, V) — logits[:, i] is
        bit-identical to what ``decode_step`` would produce after
        sequentially consuming tokens[:, :i+1] (the multi-token masked
        attention reuses the prefill path at width W against the live
        cache). Positions come from ``state.pos``; the W new KV rows are
        written at pos..pos+W-1 (dropped out-of-bounds for inactive rows).
        Returns (logits, new DecodeState with pos advanced by W) — callers
        that reject a suffix simply roll ``pos`` back (see
        ``spec_verify``); the over-written KV rows stay recoverable by
        index. Attention/MLP/MoE archs only: recurrent state cannot roll
        back (``transformer.sub_verify`` raises)."""
        params = _as_tree(params)
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens)
        new_layers = []
        for g, gp, c in zip(cfg.decoder_program(),
                            params["decoder"]["groups"], state.layers):
            x, nc = tf.group_verify(gp, x, g, cfg, c, state.pos,
                                    active=active)
            new_layers.append(nc)
        W = tokens.shape[1]
        adv = W if active is None else W * active.astype(jnp.int32)
        return self._head(params, x), DecodeState(tuple(new_layers),
                                                  state.pos + adv)

    def generate(self, params, batch, max_new_tokens: int, *,
                 key=None, temperature: float = 0.0, top_k: int = 0,
                 prompt_lens: Optional[jax.Array] = None,
                 cache_len: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 gen_lens: Optional[jax.Array] = None, pad_id: int = 0,
                 sampling=None):
        """Jit-resident generation: prefill + a ``lax.scan`` over decode
        steps with the DecodeState as donated carry and in-jit sampling.
        Returns (tokens (B, max_new_tokens) i32, final DecodeState).

        ``sampling`` takes a ``launch.api.SamplingParams`` (duck-typed to
        keep the model layer free of launch imports) and overrides the
        loose ``temperature``/``top_k``/``eos_id``/``pad_id`` kwargs, which
        remain for backward compatibility.

        Wrap in ``jax.jit`` with static ``max_new_tokens`` / ``temperature``
        / ``top_k`` / ``cache_len`` — the whole token loop then lowers to one
        XLA while-loop: no per-token dispatch, no per-step cache allocation
        (the scan carry is double-buffered once, not per token).

        Early exit: ``eos_id`` and/or per-request budgets ``gen_lens (B,)
        i32`` (clamped to ``max_new_tokens``) carry a ``done`` mask through
        the scan — finished rows freeze ``pos``, stop writing KV, and emit
        ``pad_id``, so no request pays another row's decode length in
        anything but (masked) scan slots. The EOS token itself is emitted;
        pre-done tokens are bit-identical to the un-masked scan (rows are
        batch-independent). With both None the pre-existing un-masked
        lowering is used unchanged."""
        if sampling is not None:
            temperature = sampling.temperature
            top_k = sampling.top_k
            eos_id = sampling.eos_id
            pad_id = sampling.pad_id
        params = _as_tree(params)
        B, T = batch["tokens"].shape
        F = self._prefix_len
        if cache_len is None:
            cache_len = F + T + max_new_tokens
        assert cache_len >= F + T + max_new_tokens, (
            f"cache_len {cache_len} < {F}+{T}+{max_new_tokens}")
        if key is None:
            key = jax.random.PRNGKey(0)
        keys = jax.random.split(key, max_new_tokens)  # one subkey per token
        logits, state = self.prefill(params, batch, cache_len,
                                     prompt_lens=prompt_lens)
        tok = sample_logits(logits[:, -1], keys[0], temperature, top_k)[:, None]

        if eos_id is None and gen_lens is None:       # closed-batch fast path
            def body(carry, k):
                state, tok = carry
                logits, state = self.decode_step(params, state, tok)
                nxt = sample_logits(logits[:, -1], k, temperature,
                                    top_k)[:, None]
                return (state, nxt), tok[:, 0]

            if max_new_tokens == 1:
                return tok, state
            (state, last), toks = jax.lax.scan(body, (state, tok), keys[1:])
            return jnp.concatenate([toks.T, last], axis=1), state

        if gen_lens is None:
            budget = jnp.full((B,), max_new_tokens, jnp.int32)
        else:
            budget = jnp.minimum(gen_lens.astype(jnp.int32), max_new_tokens)
        done = budget <= 1
        if eos_id is not None:
            done = done | (tok[:, 0] == eos_id)

        def body(carry, k):
            state, tok, done, n = carry
            run = ~done
            logits, state = self.decode_step(params, state, tok, active=run)
            nxt = sample_logits(logits[:, -1], k, temperature, top_k)
            n = n + run.astype(jnp.int32)
            done = done | (run & (n >= budget))
            if eos_id is not None:
                done = done | (run & (nxt == eos_id))
            emit = jnp.where(run, nxt, pad_id)
            tok = jnp.where(run, nxt, tok[:, 0])[:, None]
            return (state, tok, done, n), emit

        if max_new_tokens == 1:
            return tok, state
        carry = (state, tok, done, jnp.ones((B,), jnp.int32))
        (state, *_), emits = jax.lax.scan(body, carry, keys[1:])
        return jnp.concatenate([tok, emits.T], axis=1), state

    # -------------------------------------------- slot-pool serving (§10) --
    def init_slot_state(self, max_slots: int, cache_len: int) -> SlotState:
        """Empty slot-pool arena: every slot free (active=False)."""
        B = max_slots
        return SlotState(
            state=self.init_decode_state(B, cache_len),
            tok=jnp.zeros((B, 1), jnp.int32),
            active=jnp.zeros((B,), bool),
            done=jnp.zeros((B,), bool),
            n_gen=jnp.zeros((B,), jnp.int32),
            budget=jnp.zeros((B,), jnp.int32))

    def prefill_into(self, params, slots: SlotState, batch, slot_idx,
                     budget, key, *, cache_len: int, prompt_lens=None,
                     temperature: float = 0.0, top_k: int = 0,
                     eos_id: Optional[int] = None):
        """Prefill a (small, fixed-shape) batch of new requests and scatter
        the resulting rows into the slot pool at ``slot_idx (Bp,) i32``.

        Rows with ``slot_idx >= max_slots`` are padding (the host pads
        admission groups to a fixed prefill batch so compiles stay one per
        prompt bucket); their scatters land out of bounds and are DROPPED,
        so dummy rows never touch the arena. Samples each new request's
        first token from the prefill logits (one fold per row would change
        the stream — the whole group shares ``key`` exactly like a closed
        batch). ``cache_len`` must be the POOL's cache length: the prefill
        rows are scattered into the arena whole, so their shapes must match
        slot rows exactly. Returns (tok0 (Bp,) i32, new SlotState)."""
        params = _as_tree(params)
        slot_idx = jnp.asarray(slot_idx, jnp.int32)
        budget = jnp.asarray(budget, jnp.int32)
        logits, new_state = self.prefill(params, batch, cache_len,
                                         prompt_lens=prompt_lens)
        tok0 = sample_logits(logits[:, -1], key, temperature, top_k)
        done0 = budget <= 1
        if eos_id is not None:
            done0 = done0 | (tok0 == eos_id)
        Bp = tok0.shape[0]

        def scat_row(pool_leaf, new_leaf):       # batch dim 1 (layer-stacked)
            return pool_leaf.at[:, slot_idx].set(
                new_leaf.astype(pool_leaf.dtype), mode="drop")

        layers = jax.tree_util.tree_map(scat_row, slots.state.layers,
                                        new_state.layers)
        ones = jnp.ones((Bp,), bool)
        return tok0, SlotState(
            state=DecodeState(
                layers,
                slots.state.pos.at[slot_idx].set(new_state.pos, mode="drop")),
            tok=slots.tok.at[slot_idx].set(tok0[:, None], mode="drop"),
            active=slots.active.at[slot_idx].set(ones, mode="drop"),
            done=slots.done.at[slot_idx].set(done0, mode="drop"),
            n_gen=slots.n_gen.at[slot_idx].set(
                jnp.ones((Bp,), jnp.int32), mode="drop"),
            budget=slots.budget.at[slot_idx].set(budget, mode="drop"))

    def decode_segment(self, params, slots: SlotState, key, *, seg_len: int,
                       temperature: float = 0.0, top_k: int = 0,
                       eos_id: Optional[int] = None, pad_id: int = 0):
        """Advance the whole slot pool ``seg_len`` decode steps in ONE
        fixed-shape jitted program (a lax.scan, slot arrays in the carry).

        Per step, only ``run = active & ~done`` slots consume their token,
        write KV, and advance ``pos``; rows that hit EOS or their budget
        flip ``done`` mid-segment and coast (emitting ``pad_id``) until the
        host retires them between segments. Returns
        (emitted (max_slots, seg_len) i32, new SlotState); for slot b the
        real tokens of the segment are the first
        ``n_gen_after[b] − n_gen_before[b]`` entries of ``emitted[b]``
        (``done`` is monotone within a segment, so real tokens are always a
        prefix)."""
        params = _as_tree(params)
        keys = jax.random.split(key, seg_len)

        def body(st, k):
            run = st.run
            logits, dstate = self.decode_step(params, st.state, st.tok,
                                              active=run)
            nxt = sample_logits(logits[:, -1], k, temperature, top_k)
            n_gen = st.n_gen + run.astype(jnp.int32)
            done = st.done | (run & (n_gen >= st.budget))
            if eos_id is not None:
                done = done | (run & (nxt == eos_id))
            emit = jnp.where(run, nxt, pad_id)
            tok = jnp.where(run, nxt, st.tok[:, 0])[:, None]
            return SlotState(dstate, tok, st.active, done, n_gen,
                             st.budget), emit

        slots, emitted = jax.lax.scan(body, slots, keys)
        return emitted.T, slots

    # ------------------------------------- speculative decoding (§11) ------
    def init_spec_state(self, draft_model: "Model", max_slots: int,
                        cache_len: int) -> SpecState:
        """Paired empty pools: target slot arena + draft cache arena over
        the same (max_slots, cache_len) grid."""
        return SpecState(
            slots=self.init_slot_state(max_slots, cache_len),
            draft=draft_model.init_decode_state(max_slots, cache_len))

    def prefill_state_into(self, params, pool: DecodeState, batch, slot_idx,
                           *, cache_len: int, prompt_lens=None):
        """``prefill_into`` for a bare cache pool (the DRAFT half of
        speculative decoding): prefill the batch and scatter the rows into
        the pool at ``slot_idx`` — no sampling, no liveness bookkeeping
        (the target's SlotState is authoritative for both pools). Dummy
        rows (slot_idx >= max_slots) drop out of bounds as usual."""
        params = _as_tree(params)
        slot_idx = jnp.asarray(slot_idx, jnp.int32)
        _, new_state = self.prefill(params, batch, cache_len,
                                    prompt_lens=prompt_lens)

        def scat_row(pool_leaf, new_leaf):   # batch dim 1 (layer-stacked)
            return pool_leaf.at[:, slot_idx].set(
                new_leaf.astype(pool_leaf.dtype), mode="drop")

        layers = jax.tree_util.tree_map(scat_row, pool.layers,
                                        new_state.layers)
        return DecodeState(
            layers, pool.pos.at[slot_idx].set(new_state.pos, mode="drop"))

    def draft_propose(self, params, draft: DecodeState, tok, pos, run,
                      *, spec_k: int):
        """Greedy k-token proposal scan over the draft pool (ONE fixed-shape
        jitted program, the draft twin of ``decode_segment``).

        ``tok``/``pos``/``run`` come from the TARGET's SlotState — the
        draft's own ``pos`` is overwritten, which is exactly how rejected
        speculation rolls the draft pool back (its stale KV rows beyond the
        target's committed ``pos`` are unreachable by mask). The scan runs
        ``spec_k + 1`` steps so the draft also consumes its own last
        proposal: its KV then covers every position the target can commit,
        accept-all included. Returns (proposals (B, spec_k) i32, new
        DecodeState)."""
        params = _as_tree(params)
        state = DecodeState(draft.layers,
                            jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                             (tok.shape[0],)))

        def body(carry, _):
            st, tk = carry
            logits, st = self.decode_step(params, st, tk, active=run)
            nxt = greedy_tokens(logits[:, -1])
            tk = jnp.where(run, nxt, tk[:, 0])[:, None]
            return (st, tk), nxt

        (state, _), props = jax.lax.scan(body, (state, tok), None,
                                         length=spec_k + 1)
        return props.T[:, :spec_k], state

    def spec_verify(self, params, slots: SlotState, proposals, *,
                    eos_id: Optional[int] = None, pad_id: int = 0):
        """ONE batched target forward verifies the draft's proposals for
        every live slot, commits the accepted prefix and rolls back the
        rejected suffix — greedy only (argmax makes the rejection-sampling
        guarantee an exact prefix match, so committed streams are
        bit-identical to non-speculative greedy decode).

        Per running slot with current token w0 = ``tok`` and proposals
        w1..wk: the width-(k+1) verify forward yields target greedy tokens
        t0..tk where t_i conditions on w0..w_i. w_{i+1} is accepted iff
        w_{j+1} == t_j for all j <= i; with ``a`` accepted the candidate
        commit stream is w1..wa, t_a (the bonus token) — between 1 and k+1
        new tokens per launch — truncated by the first EOS and the
        remaining budget exactly like ``decode_segment``. Rollback is
        structural: ``pos`` is set to the committed length (the verify
        forward's extra KV rows beyond it are never attended and are
        re-written when the slot advances), ``tok`` becomes the last
        committed token (pending, not yet consumed — EOS included).

        Returns (emitted (max_slots, k+1) i32, new SlotState) under the
        same n_gen-delta protocol as ``decode_segment``: slot b's real
        tokens are the first ``n_gen_after[b] − n_gen_before[b]`` entries
        of ``emitted[b]``, the rest is ``pad_id``."""
        params = _as_tree(params)
        proposals = jnp.asarray(proposals, jnp.int32)
        B, k = proposals.shape
        W = k + 1
        run = slots.run
        p0 = slots.state.pos
        tokens = jnp.concatenate([slots.tok, proposals], axis=1)   # (B, W)
        logits, dstate = self.decode_verify(params, slots.state, tokens,
                                            active=run)
        t = greedy_tokens(logits)                                  # (B, W)
        # a = longest accepted prefix: w_{j+1} must equal t_j
        match = (proposals == t[:, :k]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)               # (B,) 0..k
        idx = jnp.arange(W, dtype=jnp.int32)[None, :]
        # candidate commit stream: accepted proposals then the bonus token
        props_ext = jnp.concatenate(
            [proposals, jnp.zeros((B, 1), jnp.int32)], axis=1)
        cand_toks = jnp.where(idx < acc[:, None], props_ext, t)    # (B, W)
        remaining = jnp.maximum(slots.budget - slots.n_gen, 1)     # run: >=1
        cand = jnp.minimum(acc + 1, remaining)                     # (B,) >=1
        if eos_id is not None:
            is_eos = (cand_toks == eos_id) & (idx < cand[:, None])
            eos_hit = is_eos.any(axis=1)
            first_eos = jnp.argmax(is_eos, axis=1)                 # 0 if none
            m = jnp.where(eos_hit, first_eos + 1, cand)
        else:
            eos_hit = jnp.zeros((B,), bool)
            m = cand
        m = jnp.where(run, m, 0)                                   # (B,)
        emitted = jnp.where(run[:, None] & (idx < m[:, None]),
                            cand_toks, pad_id)
        last = jnp.take_along_axis(
            cand_toks, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
        n_gen = slots.n_gen + m
        done = slots.done | (run & (eos_hit | (n_gen >= slots.budget)))
        return emitted, SlotState(
            state=DecodeState(dstate.layers, p0 + m),   # structural rollback
            tok=jnp.where(run, last, slots.tok[:, 0])[:, None],
            active=slots.active,
            done=done,
            n_gen=n_gen,
            budget=slots.budget)

    # --------------------------------------------------------- dry-run IO --
    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this cell —
        weak-type-correct, shardable, no device allocation."""
        cfg = self.cfg
        B, L = shape.global_batch, shape.seq_len
        dt = jnp.dtype(cfg.dtype)
        sds = jax.ShapeDtypeStruct
        if shape.mode in ("train", "prefill"):
            text_len = L - cfg.frontend_len if cfg.family == "vlm" else L
            batch = {"tokens": sds((B, text_len), jnp.int32),
                     "labels": sds((B, text_len), jnp.int32)}
            if cfg.family == "vlm":
                batch["frontend"] = sds((B, cfg.frontend_len, cfg.d_model), dt)
            if cfg.is_encdec:
                batch["frontend"] = sds((B, cfg.frontend_len, cfg.d_model), dt)
            return batch
        # decode: one token against a state of cache length L
        state = jax.eval_shape(lambda: self.init_decode_state(B, L))
        return {"token": sds((B, 1), jnp.int32), "state": state}


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
