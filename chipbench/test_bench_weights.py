"""A tensor made alone, inside another computation, takes the values that
``weights.generate`` gives it: the output check makes the initial weights
anew one tensor at a time rather than hold a copy beside the state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, smoke, spec, weights
from chipbench.reference import dense_gqa

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEED = 2 ** 32 + 29


@pytest.mark.parametrize("name", CELLS)
def test_tensor_matches_generate(name):
    dm = dense_gqa.dims_of(smoke.cell(name).config)
    key = harness.keys(SEED)[0]
    whole = weights.generate(key, dm, jnp.bfloat16)
    for k in weights.shapes(dm):
        # widened inside the computation that makes it, as the readout does
        alone = jax.jit(lambda key: weights.tensor(
            key, dm, k, jnp.bfloat16).astype(jnp.float32) * 1.0)(key)
        np.testing.assert_array_equal(
            np.asarray(alone), np.asarray(whole[k].astype(jnp.float32)), k)


def test_change_norms_read_the_change_from_the_initial_weights():
    cell = smoke.cell(CELLS[0])
    prog = harness.TrainProgram(cell)
    key_w = harness.keys(SEED)[0]
    state = prog.init_state(key_w)
    zero = prog.change_norms(state, key_w)
    assert set(zero) == set(weights.shapes(prog.dims))
    assert all(v == 0.0 for v in zero.values()), zero
    # measured from another seed's weights, the change is their distance
    key_1 = harness.keys(SEED + 1)[0]
    w0, w1 = (weights.generate(k, prog.dims, jnp.bfloat16)
              for k in (key_w, key_1))
    for k, v in prog.change_norms(state, key_1).items():
        want = float(jnp.linalg.norm(w0[k].astype(jnp.float32)
                                     - w1[k].astype(jnp.float32)))
        assert v == pytest.approx(want, rel=1e-5), k
