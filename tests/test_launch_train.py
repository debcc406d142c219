"""``repro.launch.train.main`` end to end at smoke size: ``--layers``
depth cuts, the ahead-of-time compiled step and what ``TrainRun``
reports."""
import pytest

from repro.configs import get_config, with_layers
from repro.launch import train


def test_with_layers_cuts_whole_periods():
    cfg = get_config("granite-3-2b")
    cut = with_layers(cfg, 8)
    assert cut.n_layers == 8
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.d_ff,
            cut.vocab_size) == (2048, 32, 8, 8192, 49155)
    assert with_layers(cfg, None) is cfg
    jamba = get_config("jamba-1.5-large-398b")
    with pytest.raises(ValueError, match="period"):
        with_layers(jamba, jamba.attn_every + 1)
    with pytest.raises(ValueError, match="period"):
        with_layers(cfg, 0)


@pytest.mark.parametrize("bucketed", [False, True])
def test_main_returns_compiled_run(tmp_path, bucketed):
    argv = ["--arch", "granite-3-2b", "--smoke", "--layers", "1",
            "--steps", "3", "--seq-len", "32", "--batch", "2",
            "--log-every", "1", "--ckpt-dir", str(tmp_path)]
    run = train.main(argv + (["--bucketed"] if bucketed else []))
    assert [h["step"] for h in run.history] == [1, 2, 3]
    assert len(run.step_s) == 3 and run.compile_s > 0
    assert run.compiled.as_text()          # the executable the loop ran
    assert (tmp_path / "latest").read_text() == "3"


def test_profiled_steps_carry_the_host_spans(tmp_path):
    from jax.profiler import ProfileData
    trace_dir = tmp_path / "trace"
    train.main(["--arch", "gpt-tiny", "--smoke", "--steps", "4",
                "--seq-len", "32", "--batch", "2", "--ckpt-dir",
                str(tmp_path / "ck"), "--trace-dir", str(trace_dir),
                "--trace-steps", "1:3"])
    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                spans.setdefault(e.name, []).append(e)
    assert sorted(dict(e.stats)["step_num"] for e in spans["train"]) == [1, 2]
    assert len(spans["repro.step"]) == len(spans["repro.batch"]) == 2
    assert len(spans["repro.log"]) == 2


def test_step_range_is_parsed():
    assert train.parse_args(["--trace-steps", "5:9"]).trace_steps == (5, 9)
    for bad in ("5", "9:5", "a:b"):
        with pytest.raises(SystemExit):
            train.parse_args(["--trace-steps", bad])


@pytest.mark.parametrize("bucketed", [False, True])
def test_jitted_init_state_equals_the_eager_one(bucketed):
    """Equal but for XLA's fusing of the float32 normal sampling ahead of
    the bf16 rounding: a rare element one bf16 step apart."""
    import jax
    import numpy as np
    from repro.train import train_loop
    argv = ["--arch", "gpt-tiny"] + (["--bucketed"] if bucketed else [])
    _, model, opt, *_ = train.build(train.parse_args(argv))
    jitted = train.init_state(model, opt, 3)
    eager = train_loop.init_state(model, opt, jax.random.PRNGKey(3))
    assert (jax.tree_util.tree_structure(jitted)
            == jax.tree_util.tree_structure(eager))
    a, b = jax.tree_util.tree_leaves(jitted), jax.tree_util.tree_leaves(eager)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        x32 = np.asarray(x).astype(np.float32)
        y32 = np.asarray(y).astype(np.float32)
        np.testing.assert_allclose(x32, y32, rtol=2.0 ** -7, atol=0)
        assert np.mean(x32 != y32) < 1e-4
