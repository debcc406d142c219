"""The training path's Pallas kernels compile for a TPU v5e chip.

Each case compiles one kernel at granite-3-2b widths (flash also at
internlm2-1.8b's, head dim 128) for a described (not attached) v5e chip
and asserts the Mosaic kernel is in the compiled program
(``tpu_custom_call``): what interpret-mode tests cannot show —
block shapes the (8, 128) tiling refuses, primitives Mosaic cannot lower,
VMEM overflow. Nothing runs, so no result or time is checked here.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU compiler, and every worker
collects the same tests. Skips where no v5e topology can be described."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.collage_update.collage_update import (
    collage_bucket_update, field_dtype, state_fields)
from repro.kernels.flash_attention.flash_attention import flash_mha

BUCKET = 4 * 1024 * 1024            # elements of one optimizer bucket
B, H, HKV, L, DH = 1, 32, 8, 4096, 64   # granite-3-2b heads at L=4096
INTERNLM2 = (16, 8, 2048, 128)          # internlm2-1.8b heads at L=2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name):
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln and name in ln]
    assert calls, f"no {name} tpu_custom_call in the compiled program"


@pytest.mark.parametrize("metrics", [False, True])
@pytest.mark.parametrize("code", ["C", "SR", "D"])
def test_collage_update_compiles(one_chip, code, metrics):
    state = {f: _spec((BUCKET,), field_dtype(f, code), one_chip)
             for f in state_fields(code)}
    g = _spec((BUCKET,), jnp.bfloat16, one_chip)
    scalar = _spec((), jnp.float32, one_chip)
    seed = _spec((), jnp.uint32, one_chip) if code == "SR" else None

    def step(state, g, lr, bc1, bc2, seed):
        return collage_bucket_update(state, g, lr, bc1, bc2, seed,
                                     strategy=code, compute_metrics=metrics,
                                     interpret=False)

    compiled = jax.jit(step).lower(state, g, scalar, scalar, scalar,
                                   seed).compile()
    _assert_kernel(compiled, "collage_update")


# granite's cases keep their ids; internlm2 adds head dim 128 (bf16 tiles
# and the products' dimension numbers at both head widths)
@pytest.mark.parametrize("dims,pass_,window", [
    pytest.param((H, HKV, L, DH), p, w, id=f"{p}-{w}")
    for p in ("fwd", "bwd") for w in (0, 1024)] + [
    pytest.param(INTERNLM2, p, w, id=f"internlm2-{p}-{w}")
    for p in ("fwd", "bwd") for w in (0, 1024)])
def test_flash_compiles(one_chip, dims, pass_, window):
    h, hkv, seq, dh = dims
    q = _spec((B, h, seq, dh), jnp.bfloat16, one_chip)
    kv = _spec((B, hkv, seq, dh), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_mha(q, k, v, causal=True, window=window,
                         interpret=False)

    fn = fwd if pass_ == "fwd" else jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    for name in (("flash_fwd",) if pass_ == "fwd"
                 else ("flash_fwd", "flash_dq", "flash_dkv")):
        _assert_kernel(compiled, name)
