"""The reduction from the program's named scopes to the train step's phases.

On a trace recorded on a TPU v5 lite (``fixtures/trace_granite_2l_scopes
.json.gz``: granite-3-2b at 2 layers, 1 × 4096, three steps under
``--remat full``, with the compiled program's text) the phases add up to
the device's busy time, each Mosaic kernel falls in the phase it belongs
to, the phase readers of ``chipbench/metrics`` add up to the busy share,
and a scope's time read by name agrees with the phases'; the precedence
of the phases, fused ops whose names disagree, and the ops the compiler
adds without a name, on small hand-made programs."""
import gzip
import json
import pathlib

import pytest

from chipbench import run, scopes, spec, trace

FIXTURE = (pathlib.Path(__file__).parent / "fixtures"
           / "trace_granite_2l_scopes.json.gz")
KERNELS = ("collage_update", "flash_fwd", "flash_dq", "flash_dkv")


@pytest.fixture(scope="module")
def recorded():
    fx = json.loads(gzip.open(FIXTURE, "rt").read())
    events = {"devices": {int(k): v for k, v in fx["devices"].items()},
              "host": fx["host"]}
    return events, fx["hlo"]


def test_phases_add_up_to_the_busy_time(recorded):
    events, hlo = recorded
    got = scopes.phase_seconds(events, scopes.phase_map(hlo))
    s = trace.summarize(events, trace.kernel_ops(hlo, KERNELS))
    assert sum(got["phases"].values()) == pytest.approx(s.busy_s, rel=0.005)
    named = sum(v for k, v in got["phases"].items() if k != "unattributed")
    assert got["phases"]["unattributed"] + got["mixed"] < 0.05 * s.busy_s
    assert 0 < got["head"] < named
    assert len(got["scopes"]) == 10
    assert got["scopes"] == sorted(got["scopes"], key=lambda kv: -kv[1])


PHASE_READERS = ("forward_share", "recompute_share", "backward_share",
                 "optimizer_share", "bucket_views_share")


def facts(events, hlo):
    return run.Facts(
        family=None, dims=None, traffic={}, chips=1, peaks={}, steps=3,
        window_s=0.0, tokens_per_step=0, optimizer_bytes=0,
        summary=trace.summarize(events, trace.kernel_ops(hlo, KERNELS)),
        phases=scopes.phase_seconds(events, scopes.phase_map(hlo)),
        events=events, scope_paths=scopes.scope_paths(hlo))


def test_phase_readers_add_up_to_the_busy_share(recorded):
    f = facts(*recorded)
    shares = {n: spec.metric_reader(n)(f) for n in PHASE_READERS}
    window = f.summary.window_s
    unattributed = 100 * f.phases["phases"]["unattributed"] / window
    busy = 100 * f.summary.busy_s / window
    assert sum(shares.values()) + unattributed == pytest.approx(busy,
                                                                rel=0.01)
    assert all(v > 0 for v in shares.values()), shares
    head = spec.metric_reader("lm_head_share")(f)
    assert 0 < head < busy
    # the head's time lies in the phases, not beside them
    assert head == pytest.approx(100 * f.phases["head"] / window)


def test_readers_read_nothing_without_scopes(recorded):
    events, hlo = recorded
    f = facts(events, hlo)
    f.phases = None
    for n in PHASE_READERS + ("lm_head_share", "grad_exchange_share"):
        assert spec.metric_reader(n)(f) is None, n


def test_a_scope_read_by_name_is_the_phases_entries(recorded):
    events, hlo = recorded
    f = facts(events, hlo)
    ranked = dict(scopes.phase_seconds(events, scopes.phase_map(hlo),
                                       top=1000)["scopes"])
    mlp = [ranked[f"{p}/mlp"] for p in ("forward", "recompute", "backward")]
    assert all(v > 0 for v in mlp)
    assert f.scope_s("mlp") == pytest.approx(sum(mlp), rel=1e-12)
    # a scope on the path above the leaf: every phase of the model
    assert f.scope_s("forward") > f.scope_s("mlp") + f.scope_s("attention")
    assert f.scope_s("no_such_scope") is None


def test_kernels_fall_in_their_phase(recorded):
    events, hlo = recorded
    pm = scopes.phase_map(hlo)
    kernels = trace.kernel_ops(hlo, KERNELS)
    calls: dict = {}
    for name, _, _ in events["devices"][0]:
        if name in kernels:
            key = (kernels[name], pm[name].phase)
            calls[key] = calls.get(key, 0) + 1
    # three steps of two layers: the forward, and its recomputation in
    # the backward, each call flash_fwd once a layer
    assert calls == {("collage_update", "optimizer"): 3,
                     ("flash_fwd", "forward"): 6,
                     ("flash_fwd", "recompute"): 6,
                     ("flash_dq", "backward"): 6,
                     ("flash_dkv", "backward"): 6}


@pytest.mark.parametrize("path,phase,leaf", [
    ("jit(step)/shard_map/optimizer/grad_reduce/reduce_scatter",
     "grad_exchange", "grad_reduce"),
    ("jit(step)/shard_map/param_gather/all_gather", "grad_exchange",
     "param_gather"),
    ("jit(train_step)/optimizer/jit(collage_bucket_update)/pallas_call",
     "optimizer", "optimizer"),
    ("jit(train_step)/transpose(jvp(bucket_views))/pad", "bucket_views",
     "bucket_views"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/dot_general", "recompute", "mlp"),
    ("jit(train_step)/transpose(jvp(forward))/head/mul", "backward", "head"),
    ("jit(train_step)/jvp(forward)/embed/jit(_take)/gather", "forward",
     "embed"),
    ("jit(train_step)/attention/sin", "forward", "attention"),
    ("jit(train_step)/add", "unattributed", "-"),
])
def test_precedence_of_the_phases(path, phase, leaf):
    assert scopes.phase_of(path) == phase
    assert scopes.leaf_of(path) == leaf


PROGRAM = """HloModule m

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg = f32[4]{0} negate(%p), metadata={op_name="jit(f)/optimizer/n"}
}

%packed (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  %add = f32[4]{0} add(%q, %q), metadata={op_name="jit(f)/transpose(jvp(bucket_views))/add_any"}
  %cvt = f32[4]{0} convert(%add)
  ROOT %view = f32[4]{0} bitcast(%cvt), metadata={op_name="jit(f)/optimizer/reshape"}
}

%body (b: f32[4]) -> f32[4] {
  %b = f32[4]{0} parameter(0)
  ROOT %acc = f32[4]{0} add(%b, %b)
}

%cond (c: f32[4]) -> pred[] {
  %c = f32[4]{0} parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %copy.1 = f32[4]{0} copy(%x)
  %fusion.1 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused, \
metadata={op_name="mul;jit(f)/jvp(forward)/mlp/mul;jit(f)/transpose(jvp(forward))/mlp/mul"}
  %while.1 = f32[4]{0} while(%fusion.1), condition=%cond, body=%body, \
metadata={op_name="jit(f)/transpose(jvp(forward))/head/scatter-add"}
  %fusion.3 = f32[4]{0} fusion(%while.1), kind=kLoop, calls=%packed, \
metadata={op_name="jit(f)/optimizer/reshape"}
  ROOT %fusion.2 = f32[4]{0} fusion(%fusion.3), kind=kLoop, calls=%fused, \
metadata={op_name="jit(f)/optimizer/sub"}
}
"""


def test_mixed_borrowed_and_looped_ops():
    pm = scopes.phase_map(PROGRAM)
    # a fused op whose names disagree: the phase of the first that names
    # one, and mixed
    assert pm["fusion.1"] == scopes.Scope("forward", "mlp", False, True)
    # a copy the compiler added takes the names of the op it feeds
    assert pm["copy.1"] == pm["fusion.1"]
    # a loop's body without names takes the loop's
    assert pm["acc"] == pm["while.1"] == scopes.Scope("backward", "head",
                                                      True, False)
    assert pm["fusion.2"].phase == "optimizer"
    # a fusion ending in a bitcast: named by what it computes
    assert pm["fusion.3"] == scopes.Scope("bucket_views", "bucket_views",
                                          False, False)
    assert "neg" not in pm            # inside a fusion: never its own event
    events = {"host": [["chipbench.window", 10, 100]],
              "devices": {0: [["fusion.1", 0, 20],      # half inside
                              ["while.1", 20, 40],
                              ["acc", 30, 20],          # nested in the loop
                              ["fusion.2", 70, 10],
                              ["other.1", 80, 10]]}}    # not in the map
    got = scopes.phase_seconds(events, pm)
    assert got["phases"]["forward"] == pytest.approx(10e-9)
    assert got["phases"]["backward"] == pytest.approx(40e-9)
    assert got["phases"]["optimizer"] == pytest.approx(10e-9)
    assert got["phases"]["unattributed"] == pytest.approx(10e-9)
    assert got["head"] == pytest.approx(40e-9)
    assert got["mixed"] == pytest.approx(10e-9)
    assert got["scopes"][0] == ["backward/head", pytest.approx(40e-9)]


def test_a_program_without_the_scopes_gives_no_phases():
    unscoped = PROGRAM
    for name in scopes.SCOPES:
        unscoped = unscoped.replace(name, "f")
    assert scopes.phase_map(unscoped) == {}
    events = {"host": [["chipbench.window", 0, 10]],
              "devices": {0: [["fusion.1", 0, 5]]}}
    assert scopes.phase_seconds(events, {}) is None
