"""The reduction from a profiler trace to device times.

On a trace recorded on a TPU v5 lite (``fixtures/trace_granite_2l.json.gz``:
granite-3-2b at 2 layers, 1 × 4096, three steps) the busy time, kernel
times and calls are checked against counts made another way, and the
exposed-collective arithmetic on small hand-made traces."""
import gzip
import json
import pathlib

import pytest

from chipbench import trace

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_granite_2l.json.gz"
KERNELS = ("collage_update", "flash_fwd", "flash_dq", "flash_dkv")


@pytest.fixture(scope="module")
def recorded():
    fx = json.loads(gzip.open(FIXTURE, "rt").read())
    events = {"devices": {int(k): v for k, v in fx["devices"].items()},
              "host": fx["host"]}
    kernels = trace.kernel_ops("\n".join(fx["hlo_kernel_lines"]), KERNELS)
    return events, kernels


def sweep_busy(intervals, lo, hi):
    """Covered length by a sweep over interval ends (not by merging)."""
    marks = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e in intervals
                      if e > lo and s < hi])
    covered, depth, last = 0.0, 0, None
    for x, d in marks:
        if depth > 0:
            covered += x - last
        depth += d
        last = x
    return covered


def test_kernel_instructions_are_named_from_the_hlo(recorded):
    _, kernels = recorded
    assert sorted(set(kernels.values())) == sorted(KERNELS)
    assert kernels["flash_fwd.16"] == "flash_fwd"
    assert len(kernels) == 5       # two flash_fwd (forward and remat)


def test_busy_and_idle_on_the_recorded_trace(recorded):
    events, kernels = recorded
    s = trace.summarize(events, kernels)
    (win,) = [h for h in events["host"] if h[0] == "chipbench.window"]
    w0, w1 = win[1], win[1] + win[2]
    ops = events["devices"][0]
    busy = sweep_busy([(o[1], o[1] + o[2]) for o in ops], w0, w1)
    assert s.chips == 1
    assert s.window_s == pytest.approx(win[2] / 1e9)
    assert s.busy_s == pytest.approx(busy / 1e9, rel=1e-12)
    idle = 1 - s.busy_s / s.window_s
    assert 0.0 < idle < 0.05          # a host sync between 145 ms steps


def test_kernel_time_and_calls_on_the_recorded_trace(recorded):
    events, kernels = recorded
    s = trace.summarize(events, kernels)
    for k in KERNELS:
        evs = [o for o in events["devices"][0]
               if o[0].split(".")[0] == k]
        assert s.kernel_calls[k] == len(evs)
        assert s.kernel_s[k] == pytest.approx(sum(o[2] for o in evs) / 1e9)
    # three steps of two layers: forward and remat forward per layer
    assert s.kernel_calls == {"flash_fwd": 12, "flash_dq": 6,
                              "flash_dkv": 6, "collage_update": 3}
    assert s.collective_s == 0 and s.exposed_collective_s == 0


def test_breakdown_uses_self_time(recorded):
    events, kernels = recorded
    s = trace.summarize(events, kernels)
    names = [n for n, _ in s.device_ops]
    assert "while" not in names        # a loop's time is its body's
    assert set(KERNELS) <= set(names)
    total_self = sum(t for _, t in s.device_ops)
    assert total_self <= s.busy_s * (1 + 1e-9)
    gaps = s.idle_gaps
    assert len(gaps) <= 10 and gaps[0][0] in ("block", "dispatch", "batch")
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def _events(ops, window=(0, 40)):
    return {"devices": {0: [list(o) for o in ops]},
            "host": [["chipbench.window", window[0], window[1] - window[0]]]}


def test_exposed_collective_excludes_what_compute_covers():
    ops = [("while.1", 0, 30),            # holds the rest
           ("fusion.1", 0, 10),
           ("all-gather-start.1", 5, 10),  # 5..15: half under fusion.1
           ("reduce-scatter.2", 20, 5)]    # 20..25: nothing else runs
    s = trace.summarize(_events(ops), {})
    assert s.busy_s == pytest.approx(30e-9)
    assert s.collective_s == pytest.approx(15e-9)
    assert s.exposed_collective_s == pytest.approx(10e-9)
    self_t, leaf = trace.nesting([list(o) for o in ops])
    assert leaf == [False, True, True, True]
    assert self_t[0] == pytest.approx(30 - 10 - 10 - 5)


def test_means_over_chips_and_the_window_clip():
    a = [("fusion.1", -10, 20), ("all-reduce.1", 10, 10)]
    b = [("fusion.1", 0, 40)]
    ev = {"devices": {0: [list(o) for o in a], 1: [list(o) for o in b]},
          "host": [["chipbench.window", 0, 40],
                   ["chipbench.block", 20, 20]]}
    s = trace.summarize(ev, {})
    assert s.chips == 2
    assert s.busy_s == pytest.approx((20 + 40) / 2 * 1e-9)
    assert s.exposed_collective_s == pytest.approx(10 / 2 * 1e-9)
    assert s.idle_gaps == [["block", pytest.approx(20e-9)]]


def test_no_device_work_is_an_error():
    with pytest.raises(RuntimeError):
        trace.summarize({"devices": {}, "host": [["chipbench.window", 0, 1]]},
                        {})
