"""The train step's named scopes (``repro.train.scopes``) reach the compiled
program, where the chip benchmark's reduction (``chipbench/scopes.py``)
gives every instruction its phase.

Each case compiles the launcher's step (``repro.launch.train.build``) on
the CPU in a process of its own, with as many host devices as the case
asks for, and reports what ``phase_map`` found."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--arch", "gpt-tiny", "--smoke", "--bucketed", "--remat", "full",
        "--precision", "C", "--seq-len", "32"]
STEP = ("forward", "recompute", "backward", "optimizer", "bucket_views")

PROBE = """
    import json, re, sys
    import jax
    from chipbench import scopes
    from repro.launch import train
    from repro.train import sharded, train_loop

    args = train.parse_args(sys.argv[1:])
    cfg, model, opt, step_fn, batch_fn, mesh, _ = train.build(args)
    key = jax.random.PRNGKey(0)
    if mesh is None:
        state = jax.eval_shape(
            lambda k: train_loop.init_state(model, opt, k), key)
    else:
        state = jax.eval_shape(
            lambda k: sharded.init_state(model, opt, k, mesh), key)
    hlo = step_fn.lower(state, batch_fn(0)).compile().as_text()
    pm = scopes.phase_map(hlo)
    entry = hlo[hlo.index("\\nENTRY "):]
    entry = entry[:entry.index("\\n}")]
    skip = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
    compute = [n for n, i in scopes.instructions(hlo).items()
               if re.search(r"%" + re.escape(n) + " = ", entry)
               and i.opcode not in skip]
    print(json.dumps({
        "phases": sorted({s.phase for s in pm.values()}),
        "leaves": sorted({s.leaf for s in pm.values()}),
        "head": any(s.head for s in pm.values()),
        "compute": len(compute),
        "unattributed": sum(pm[n].phase == "unattributed" for n in compute),
    }))
"""


def probe(argv, n_devices):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [REPO, os.path.join(REPO, "src")]),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(PROBE)]
                         + argv, capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# under shard_map the loss and metric means over the dp axis (scalars) run
# outside every scope
@pytest.mark.parametrize("argv,n_devices,exchange,unattributed", [
    (TINY + ["--batch", "2"], 1, (), 0.05),
    (TINY + ["--batch", "8", "--dp", "4", "--zero"], 4,
     ("grad_reduce", "param_gather"), 0.1),
], ids=["one-program", "dp4-zero"])
def test_every_phase_is_named_in_the_compiled_step(argv, n_devices,
                                                   exchange, unattributed):
    got = probe(argv, n_devices)
    assert set(STEP) <= set(got["phases"])
    assert ("grad_exchange" in got["phases"]) == bool(exchange)
    assert set(exchange) <= set(got["leaves"])
    assert {"embed", "attention", "mlp", "head"} <= set(got["leaves"])
    assert got["head"]
    assert got["compute"] > 50
    assert got["unattributed"] < unattributed * got["compute"], got
