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
