"""Checkpointing + fault tolerance + elasticity + data-pipeline determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.collage import CollageAdamW
from repro.core.precision import PrecisionPolicy, Strategy
from repro.data.synthetic import SyntheticCorpus, make_batch_fn
from repro.models.model import build_model
from repro.train import checkpoint as ckpt_lib
from repro.train import train_loop
from repro.train.elastic import RunSupervisor, SupervisorConfig


@pytest.fixture
def setup(tmp_path):
    cfg = get_config("gpt-tiny", smoke=True)
    model = build_model(cfg)
    opt = CollageAdamW(1e-3, b2=0.95,
                       policy=PrecisionPolicy(strategy=Strategy.C_COLLAGE_PLUS))
    shape = ShapeConfig("t", 32, 4, "train")
    batch_fn = make_batch_fn(cfg, shape)
    step = jax.jit(train_loop.make_train_step(model, opt))
    state = train_loop.init_state(model, opt, jax.random.PRNGKey(0))
    return model, opt, step, batch_fn, state, str(tmp_path / "ckpt")


def _leaves_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


class TestCheckpoint:
    def test_save_restore_bitwise(self, setup, tmp_path):
        model, opt, step, batch_fn, state, ckpt = setup
        for i in range(3):
            state, _ = step(state, batch_fn(i))
        ckpt_lib.save(ckpt, 3, state, extra={"step": 3})
        restored, extra = ckpt_lib.restore(ckpt, 3, state)
        assert extra["step"] == 3
        _leaves_equal(state, restored)

    def test_checksum_detects_corruption(self, setup):
        model, opt, step, batch_fn, state, ckpt = setup
        path = ckpt_lib.save(ckpt, 1, state, extra={"step": 1})
        # flip bytes in the array file
        f = os.path.join(path, "arrays.npz")
        data = bytearray(open(f, "rb").read())
        data[len(data) // 2] ^= 0xFF
        open(f, "wb").write(bytes(data))
        with pytest.raises(Exception):
            ckpt_lib.restore(ckpt, 1, state)

    def test_keep_last_gc_and_latest(self, setup):
        model, opt, step, batch_fn, state, ckpt = setup
        for s in (1, 2, 3, 4, 5):
            ckpt_lib.save(ckpt, s, state, keep_last=2, extra={"step": s})
        steps = sorted(d for d in os.listdir(ckpt) if d.startswith("step_"))
        assert steps == ["step_00000004", "step_00000005"]
        assert ckpt_lib.latest_step(ckpt) == 5


class TestResume:
    def test_bitwise_identical_resume(self, setup):
        """Kill at step 5, resume from ckpt@3 — must rejoin the original
        trajectory exactly (counter-based data ⇒ no replay divergence)."""
        model, opt, step, batch_fn, state, ckpt = setup
        states = {0: state}
        s = state
        for i in range(8):
            if i == 3:
                ckpt_lib.save(ckpt, 3, s, extra={"step": 3})
            s, _ = step(s, batch_fn(i))
        final_ref = s
        # resume path
        s2, extra = ckpt_lib.restore(ckpt, 3, state)
        for i in range(extra["step"], 8):
            s2, _ = step(s2, batch_fn(i))
        _leaves_equal(final_ref, s2)


class TestSupervisor:
    def test_crash_recovery(self, setup):
        model, opt, step, batch_fn, state, ckpt = setup
        crashes = {"armed": True}

        def fault(step_i):
            if step_i == 7 and crashes["armed"]:
                crashes["armed"] = False
                raise RuntimeError("simulated host failure")

        sup = RunSupervisor(SupervisorConfig(ckpt, ckpt_every=5),
                            fault_hook=fault)
        final, step_i, _ = sup.run(state, step, batch_fn, n_steps=10)
        assert step_i == 10
        # recoveries record the FAULTING step (forensics), not the
        # checkpoint it rolled back to
        assert sup.recoveries == [7]
        assert sup.stragglers == []
        # must equal an uninterrupted run
        s = state
        for i in range(10):
            s, _ = step(s, batch_fn(i))
        _leaves_equal(s, final)

    @pytest.mark.parametrize("status", ["RESOURCE_EXHAUSTED",
                                        "INVALID_ARGUMENT"])
    def test_device_oom_and_compile_errors_propagate(self, setup, status):
        """A device OOM or a compile refusal after the first checkpoint must
        surface, not be 'recovered' from the checkpoint and retried forever."""
        model, opt, step, batch_fn, state, ckpt = setup
        calls = []

        def failing_step(s, batch):
            calls.append(1)
            if len(calls) > 2:
                raise jax.errors.JaxRuntimeError(
                    f"{status}: simulated device error")
            return step(s, batch)

        sup = RunSupervisor(SupervisorConfig(ckpt, ckpt_every=1))
        with pytest.raises(jax.errors.JaxRuntimeError, match=status):
            sup.run(state, failing_step, batch_fn, n_steps=5)
        assert ckpt_lib.latest_step(ckpt) == 2
        assert sup.recoveries == [] and len(calls) == 3

    def test_straggler_keeps_completed_state(self):
        """A late-but-successful step must NOT be rolled back: the supervisor
        keeps the completed state, records the faulting step, and the run
        equals an uninterrupted one bit-for-bit (no discarded work)."""
        import time

        state = jnp.zeros((4,), jnp.float32)
        # warm the dispatch path: the first eager `s + batch` can cost tens
        # of ms and would otherwise inflate the p99 deadline window
        (state + jnp.float32(0)).block_until_ready()

        def train_step(s, batch):
            # deterministic fast steps; step 7 is a straggler, slow enough
            # to clear the deadline even if a cold-start outlier lands in
            # the p99 window (deadline ≤ ~0.1s·slack)
            if int(batch) == 7:
                time.sleep(1.0)
            else:
                time.sleep(0.002)
            return s + batch, {"loss": 0.0}

        import tempfile
        with tempfile.TemporaryDirectory() as d:
            sup = RunSupervisor(SupervisorConfig(
                d, ckpt_every=5, min_step_time=1e-4, deadline_slack=5.0))
            final, step_i, _ = sup.run(state, train_step,
                                       lambda i: jnp.float32(i), n_steps=10)
        assert step_i == 10
        assert sup.recoveries == [7] and sup.stragglers == [7]
        # straggler outliers must not poison the p99 deadline window
        assert all(t < 0.5 for t in sup.step_times)
        np.testing.assert_array_equal(np.asarray(final),
                                      np.full((4,), sum(range(10)), np.float32))


class TestPipelineResidualElasticity:
    """Checkpoint elasticity for the PIPELINE-mode EF residual layout
    (PR 5): ``TrainState.grad_err`` is a dict of per-(leaf-class × dtype)
    flat buckets whose leading dim is the stage·dp device index AND whose
    bucket LENGTH is per-stage — so a stage-count rescale changes both
    dims. Restore must zero-fill (one step of compression error), never
    fail the shape check; a same-layout restore must keep the rows."""

    def _pipeline_state(self, model, opt, S, n_dp):
        from repro.train import sharded
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        rows = sharded.pipeline_error_state(params, S, n_dp, jnp.bfloat16)
        # nonzero residuals so "preserved" and "zero-filled" are distinct
        rows = {k: (v + jnp.arange(v.shape[0], dtype=v.dtype)[:, None]
                    * jnp.asarray(0.125, v.dtype)) + jnp.asarray(0.25, v.dtype)
                for k, v in rows.items()}
        return train_loop.TrainState(params, opt_state, rows)

    def _mk(self):
        cfg = get_config("gpt-tiny", smoke=True)
        model = build_model(cfg)
        opt = CollageAdamW(1e-3, b2=0.95, policy=PrecisionPolicy(
            strategy=Strategy.C_COLLAGE_PLUS))
        return model, opt

    def test_same_layout_round_trip_keeps_rows(self, tmp_path):
        model, opt = self._mk()
        state = self._pipeline_state(model, opt, S=2, n_dp=2)
        ckpt = str(tmp_path / "ckpt")
        ckpt_lib.save(ckpt, 1, state, extra={"step": 1})
        restored, _ = ckpt_lib.restore_bucketed(ckpt, 1, state)
        _leaves_equal(state, restored)

    @pytest.mark.parametrize("new_S,new_dp", [(1, 2), (2, 4), (1, 4),
                                              (2, 1)])
    def test_zero_fills_across_stage_and_dp_changes(self, tmp_path,
                                                    new_S, new_dp):
        model, opt = self._mk()
        state = self._pipeline_state(model, opt, S=2, n_dp=2)
        ckpt = str(tmp_path / "ckpt")
        ckpt_lib.save(ckpt, 1, state, extra={"step": 1})
        template = self._pipeline_state(model, opt, S=new_S, n_dp=new_dp)
        restored, _ = ckpt_lib.restore_bucketed(ckpt, 1, template)
        # params / optimizer state restore bit-exactly regardless
        _leaves_equal(state.params, restored.params)
        for k, row in restored.grad_err.items():
            assert row.shape == template.grad_err[k].shape, k
            if row.shape == state.grad_err[k].shape:
                np.testing.assert_array_equal(
                    np.asarray(row, np.float32),
                    np.asarray(state.grad_err[k], np.float32))
            else:   # relaid-out rows zero-fill — bounded O(ulp) carry lost
                assert np.abs(np.asarray(row, np.float32)).max() == 0, k

    def test_restore_across_residual_layout_classes(self, tmp_path):
        """grad_err may change LAYOUT CLASS across resumes — pipeline
        bucket dict ↔ per-leaf tree ↔ absent (dp/stage rescale, pipeline
        on/off, compression toggle). Restore matches by name: template
        grad_err leaves with no stored counterpart zero-fill, stored ones
        the template lacks drop, everything else restores bit-exactly.
        A non-grad_err structure mismatch must still fail hard."""
        model, opt = self._mk()
        state = self._pipeline_state(model, opt, S=2, n_dp=2)
        ckpt = str(tmp_path / "ckpt")
        ckpt_lib.save(ckpt, 1, state, extra={"step": 1})
        # pipeline dict → per-leaf tree (left pipeline mode, dp EF rows)
        tree_err = jax.tree_util.tree_map(
            lambda p: jnp.zeros((4,) + p.shape, jnp.float32), state.params)
        template = train_loop.TrainState(state.params, state.opt_state,
                                         tree_err)
        restored, _ = ckpt_lib.restore_bucketed(ckpt, 1, template)
        _leaves_equal(state.params, restored.params)
        for leaf in jax.tree_util.tree_leaves(restored.grad_err):
            assert np.abs(np.asarray(leaf, np.float32)).max() == 0
        # pipeline dict → absent (compression switched off)
        template = train_loop.TrainState(state.params, state.opt_state,
                                         None)
        restored, _ = ckpt_lib.restore_bucketed(ckpt, 1, template)
        assert restored.grad_err is None
        _leaves_equal(state.params, restored.params)
        # a PARAMS structure mismatch is still a hard error
        bad_params = dict(state.params)
        bad_params["rogue"] = jnp.zeros((4,), jnp.float32)
        template = train_loop.TrainState(bad_params, state.opt_state, None)
        with pytest.raises(AssertionError, match="structure mismatch"):
            ckpt_lib.restore_bucketed(ckpt, 1, template)

    def test_supervisor_recovers_pipeline_layout_state(self, tmp_path):
        """Crash-recovery through the supervisor with the (stage·dp)-row
        grad_err dict in flight: the restore path must hand back the dict
        structure intact, and the straggler p99 window must stay sane when
        the recovery's restore cost lands in the step-time samples."""
        model, opt = self._mk()
        state = self._pipeline_state(model, opt, S=2, n_dp=2)
        crashes = {"armed": True}

        def fault(step_i):
            if step_i == 3 and crashes["armed"]:
                crashes["armed"] = False
                raise RuntimeError("simulated stage-host failure")

        def fake_step(s, batch):
            err = {k: v + jnp.asarray(0.5, v.dtype)
                   for k, v in s.grad_err.items()}
            return train_loop.TrainState(s.params, s.opt_state, err), \
                {"loss": 0.0}

        sup = RunSupervisor(SupervisorConfig(str(tmp_path / "c"),
                                             ckpt_every=2),
                            fault_hook=fault)
        final, step_i, _ = sup.run(state, fake_step,
                                   lambda i: jnp.float32(i), n_steps=6)
        assert step_i == 6 and sup.recoveries == [3]
        assert set(final.grad_err) == set(state.grad_err)
        for k, v in final.grad_err.items():
            assert v.shape == state.grad_err[k].shape, k
        # the p99 window holds one sample per completed step EXECUTION:
        # steps 0,1,2 + the crashed attempt at 3 (no sample) + the re-run
        # of 2,3 after restoring ckpt@2 + 4,5 → 7 samples, never the
        # crashed attempt itself
        assert len(sup.step_times) == 7


class TestElasticRestore:
    def test_restore_across_mesh_shapes(self, setup):
        """Save unsharded, restore into a resharded template (device_put with
        new shardings) — the elastic re-scale path (here: 1 device)."""
        model, opt, step, batch_fn, state, ckpt = setup
        ckpt_lib.save(ckpt, 1, state, extra={"step": 1})
        # template with different (here: same-device) shardings still works
        restored, _ = ckpt_lib.restore(ckpt, 1, state)
        _leaves_equal(state, restored)


class TestDataPipeline:
    def test_deterministic_and_stateless(self):
        c = SyntheticCorpus(256, 32, 8, seed=1)
        b1 = c.batch_at(5)
        b2 = c.batch_at(5)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))
        b3 = c.batch_at(6)
        assert not np.array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b3["tokens"]))

    def test_host_sharding_partitions_batch(self):
        c = SyntheticCorpus(256, 16, 8, seed=2)
        rows = [c.batch_at(0, host_id=h, n_hosts=4)["tokens"] for h in range(4)]
        assert all(r.shape == (2, 16) for r in rows)
        # distinct hosts draw distinct rows
        assert not np.array_equal(np.asarray(rows[0]), np.asarray(rows[1]))

    def test_learnable_structure(self):
        """Zipf-Markov corpus: the order-2 conditional next-token
        distribution is peaked (a model can beat uniform) — required for the
        paper-quality benchmarks."""
        c = SyntheticCorpus(256, 512, 8, seed=3)
        rows = np.asarray(c.batch_at(0)["tokens"])
        from collections import Counter, defaultdict
        cond = defaultdict(Counter)
        for row in rows:
            for i in range(2, len(row)):
                state = (int(row[i - 2]) % 64 * 31 + int(row[i - 1]) % 64) % 64
                cond[state][int(row[i])] += 1
        # average top-1 conditional frequency ≫ uniform 1/256
        tops = [max(cnt.values()) / sum(cnt.values())
                for cnt in cond.values() if sum(cnt.values()) >= 20]
        assert np.mean(tops) > 5 / 256, np.mean(tops)
