"""A model family is a file: ``chipbench/families/<family>.py``, named by
the configuration's ``"family"``.

A configuration that names no family, or one without its file, is refused
before anything is built. A stub family, written with its configuration,
limits and ``BENCHMARK.json`` entries into a temporary checkout, runs a
smoke cell end to end through the unchanged harness: the dense GQA
decoder's math under other tensor names, its layers held as two groups,
and a ``launcher`` entry in its configuration. Its losses are the dense
family's on the same values, to the bit, in the program and in the
reference."""
import json
import shutil
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import data, harness, smoke, spec, weights
from chipbench.reference import adamw, dense_gqa

SEED = 2 ** 33 + 41
DENSE_CELL = "granite-3-2b.pretrain-4k"
STUB_CELL = "stub-2l.pretrain-4k"

STUB = '''
"""The dense GQA decoder under other tensor names, each per-layer tensor
held as two groups of layers, ``lower`` and ``upper``."""
import jax.numpy as jnp

from chipbench import spec
from chipbench.reference import adamw, dense_gqa as ref

DENSE = spec.load_family("dense_gqa")
dims_of, SMOKE, check_widths = DENSE.dims_of, DENSE.SMOKE, DENSE.check_widths
train_flops_per_token = DENSE.train_flops_per_token
flash_widths = DENSE.flash_widths

GLOBAL = {"embed": "tok_embeddings", "final_norm": "norm",
          "lm_head": "output"}
PER_LAYER = {"attn_norm": "attention_norm", "wq": "attention.wq",
             "wk": "attention.wk", "wv": "attention.wv",
             "wo": "attention.wo", "mlp_norm": "ffn_norm",
             "w_gate": "feed_forward.w1", "w_up": "feed_forward.w3",
             "w_down": "feed_forward.w2"}
GROUPS = ("lower", "upper")


def shapes(dm):
    out, lower = {}, dm.layers // 2
    for name, (shape, std) in DENSE.shapes(dm).items():
        if name in GLOBAL:
            out[GLOBAL[name]] = (shape, std)
            continue
        out["lower." + PER_LAYER[name]] = ((lower,) + shape[1:], std)
        out["upper." + PER_LAYER[name]] = ((dm.layers - lower,) + shape[1:],
                                           std)
    return out


def as_dense(w):
    d = {k: w[v] for k, v in GLOBAL.items() if v in w}
    for k, v in PER_LAYER.items():
        d[k] = jnp.concatenate([w[g + "." + v] for g in GROUPS])
    return d


def from_dense(d):
    w = {v: d[k] for k, v in GLOBAL.items() if k in d}
    for k, v in PER_LAYER.items():
        lower = d[k].shape[0] // 2
        w["lower." + v], w["upper." + v] = d[k][:lower], d[k][lower:]
    return w


def to_program(w):
    return DENSE.to_program(as_dense(w))


def from_program(p):
    return from_dense(DENSE.from_program(p))


def run(make_w0, batches, opt, dm, mesh):
    theta = adamw.to_f32(make_w0())
    l1, g1 = ref.loss_and_grad(as_dense(theta), batches[0], dm, mesh)
    g1 = from_dense(g1)
    grad_norms = adamw.leaf_norms(g1)
    theta = adamw.step(theta, [g1], 1, opt)
    l2, g2 = ref.loss_and_grad(as_dense(theta), batches[1], dm, mesh)
    theta = adamw.step(theta, [g1, from_dense(g2)], 2, opt)
    l3 = ref.loss(as_dense(theta), batches[2], dm, mesh)
    return {"losses": [float(l1), float(l2), float(l3)],
            "grad_norms": grad_norms,
            "change_norms": adamw.change_norms(theta, make_w0())}
'''


def checkout(tmp_path, family="stub_gqa", source=STUB):
    """A checkout holding only the stub cell's files and entries: its
    family, configuration (granite's, with a ``launcher`` entry), limits,
    and the traffic it shares with granite."""
    here = tmp_path / "chipbench"
    for d in ("configs", "families", "traffic", "limits"):
        (here / d).mkdir(parents=True)
    shutil.copy(spec.HERE / "traffic" / "pretrain-4k.json", here / "traffic")
    shutil.copy(spec.HERE / "limits" / f"{DENSE_CELL}.json",
                here / "limits" / f"{STUB_CELL}.json")
    config = json.loads((spec.HERE / "configs" / "granite-3-2b.json")
                        .read_text())
    config.update(name="stub-2l", launcher=["--seed", "11"])
    if family is None:
        del config["family"]
    else:
        config["family"] = family
    (here / "configs" / "stub-2l.json").write_text(json.dumps(config))
    if source is not None:
        (here / "families" / f"{family}.py").write_text(source)
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "stub-2l", "source": config["source"],
                         "file": "chipbench/configs/stub-2l.json",
                         "reduced": [], "why": "a stub"}]
    bench["workloads"] = [{"name": STUB_CELL, "config": "stub-2l",
                           "traffic": "pretrain-4k", "chips": 1,
                           "why": "a stub"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.mark.parametrize("family,source,says", [
    (None, None, "names no family"),
    ("no_such_family", None, "no family file"),
    ("../reference/dense_gqa", None, "no family file"),
    ("half_a_family", "dims_of = None\n", "lacks shapes"),
])
def test_a_configuration_without_a_family_is_refused(tmp_path, family,
                                                     source, says):
    root = checkout(tmp_path, family, source)
    with pytest.raises(spec.SpecError, match=says) as e:
        spec.load_cell(STUB_CELL, root)
    assert "stub-2l.json" in str(e.value) or str(tmp_path) in str(e.value)


def dense_losses(dense_cell, stub, stub_w, check_tokens):
    """The dense family's program and reference on the stub's values."""
    prog = harness.TrainProgram(dense_cell)
    params, opt_state = prog.opt.init_bucketed(
        prog.family.to_program(stub.as_dense(stub_w)))
    from repro.train import train_loop
    state = train_loop.TrainState(params, opt_state, None)
    pool = [prog.batch(x) for x in check_tokens]
    prog.compile(state, pool[0])
    program = []
    for b in pool:
        state, met = prog.step(state, b)
        program.append(float(met["loss"]))
    t = dense_cell.traffic
    opt = adamw.AdamW(lr=t["lr"], warmup=t["warmup"], total=t["steps"],
                      b1=t["b1"], b2=t["b2"], eps=t["eps"],
                      weight_decay=t["weight_decay"])
    mesh = jax.sharding.Mesh(prog.devices, ("rows",))
    toks = [jax.device_put(x, NamedSharding(mesh, P("rows", None)))
            for x in check_tokens]
    dm = prog.dims
    reference = dense_gqa.run(lambda: stub.as_dense(stub_w), toks, opt, dm,
                              mesh)["losses"]
    return program, reference


def test_a_stub_family_runs_a_smoke_cell(tmp_path):
    cell = smoke.cell(STUB_CELL, root=checkout(tmp_path))
    stub = cell.family
    assert stub.__file__.startswith(str(tmp_path))
    # the configuration's launcher entry comes after the traffic's
    assert harness.program_argv(cell)[-2:] == ["--seed", "11"]
    res = harness.run(cell, SEED, 0.5, False, t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    dims = stub.dims_of(cell.config)
    names = set(stub.shapes(dims))
    assert {"lower.attention.wq", "upper.feed_forward.w2"} <= names
    for side in ("program", "reference"):
        read = res["readings"][side]
        assert set(read["grad_norms"]) == set(read["change_norms"]) == names

    stub_w = weights.generate(harness.keys(SEED)[0], stub.shapes(dims),
                              jnp.bfloat16)
    key_d = harness.keys(SEED)[1]
    check = data.for_traffic(key_d, dims.vocab, cell.traffic,
                             cell.traffic["pool"])[:harness.CHECK_STEPS]
    program, reference = dense_losses(smoke.cell(DENSE_CELL), stub, stub_w,
                                      check)
    assert res["readings"]["program"]["losses"] == program
    assert res["readings"]["reference"]["losses"] == reference
