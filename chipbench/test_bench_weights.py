"""A tensor made alone, inside another computation, takes the values that
``weights.generate`` gives it: the output check makes the initial weights
anew one tensor at a time rather than hold a copy beside the state. The
dense family's weights keep the values they had when the tensor table sat
in ``weights.py`` (digests recorded from that code)."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, smoke, spec, weights

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEED = 2 ** 32 + 29


@pytest.mark.parametrize("name", CELLS)
def test_tensor_matches_generate(name):
    cell = smoke.cell(name)
    table = cell.family.shapes(cell.family.dims_of(cell.config))
    key = harness.keys(SEED)[0]
    whole = weights.generate(key, table, jnp.bfloat16)
    for k in table:
        # widened inside the computation that makes it, as the readout does
        alone = jax.jit(lambda key: weights.tensor(
            key, table, k, jnp.bfloat16).astype(jnp.float32) * 1.0)(key)
        np.testing.assert_array_equal(
            np.asarray(alone), np.asarray(whole[k].astype(jnp.float32)), k)


def test_change_norms_read_the_change_from_the_initial_weights():
    cell = smoke.cell(CELLS[0])
    prog = harness.TrainProgram(cell)
    key_w = harness.keys(SEED)[0]
    state = prog.init_state(key_w)
    zero = prog.change_norms(state, key_w)
    assert set(zero) == set(prog.table)
    assert all(v == 0.0 for v in zero.values()), zero
    # measured from another seed's weights, the change is their distance
    key_1 = harness.keys(SEED + 1)[0]
    w0, w1 = (weights.generate(k, prog.table, jnp.bfloat16)
              for k in (key_w, key_1))
    for k, v in prog.change_norms(state, key_1).items():
        want = float(jnp.linalg.norm(w0[k].astype(jnp.float32)
                                     - w1[k].astype(jnp.float32)))
        assert v == pytest.approx(want, rel=1e-5), k


def digest(x) -> str:
    return hashlib.sha256(np.asarray(x).view(np.uint16).tobytes()
                          ).hexdigest()[:16]


# sha256 of the bf16 bits, first 16 hex digits, of weights made from
# harness.keys(SEED)[0] by the code before the families
SMOKE_DIGESTS = {
    "granite-3-2b.pretrain-4k": {
        "attn_norm": "5341e6b2646979a7", "embed": "9ce81728f8a304e0",
        "final_norm": "38723a2e5e8a17aa", "mlp_norm": "5341e6b2646979a7",
        "w_down": "28d2f3efcc268790", "w_gate": "aae05da98a60a911",
        "w_up": "ae23ae11577ff287", "wk": "347f1d03e58f5b26",
        "wo": "2a573cbf6757b0fe", "wq": "10b177129a9ef4ef",
        "wv": "dfcd9115b16e2441"},
    "internlm2-1.8b.sft-2k": {
        "attn_norm": "5341e6b2646979a7", "embed": "9ce81728f8a304e0",
        "final_norm": "38723a2e5e8a17aa", "lm_head": "77d94fd656e2d6e7",
        "mlp_norm": "5341e6b2646979a7", "w_down": "23296045c9dbdb09",
        "w_gate": "ae23ae11577ff287", "w_up": "42fcc39d9531460b",
        "wk": "5c0e8c870dc173cb", "wo": "10b177129a9ef4ef",
        "wq": "5d075699c95906dc", "wv": "64fd6890bc7cabe6"},
}
# two tensors at granite-3-2b's full shapes (8 layers)
FULL_DIGESTS = {"wk": "dbdc59af2f1e6540", "wo": "c8ed2136ec78b9cf"}


@pytest.mark.parametrize("name", sorted(SMOKE_DIGESTS))
def test_dense_weights_keep_their_values_at_smoke_size(name):
    cell = smoke.cell(name)
    table = cell.family.shapes(cell.family.dims_of(cell.config))
    w = weights.generate(harness.keys(SEED)[0], table, jnp.bfloat16)
    assert {k: digest(v) for k, v in w.items()} == SMOKE_DIGESTS[name]


def test_dense_weights_keep_their_values_at_full_shapes():
    cell = spec.load_cell("granite-3-2b.pretrain-4k")
    table = cell.family.shapes(cell.family.dims_of(cell.config))
    key = harness.keys(SEED)[0]
    got = {k: digest(jax.jit(lambda key: weights.tensor(
        key, table, k, jnp.bfloat16))(key)) for k in FULL_DIGESTS}
    assert got == FULL_DIGESTS
