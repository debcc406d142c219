"""Distributed correctness on 8 virtual host devices (subprocess — the main
test process keeps a single device per task constraints):

  * pjit FSDP×TP train step ≡ single-device step (numerics)
  * GPipe pipeline over a mesh axis ≡ unpipelined stack (fwd + grad)
  * compressed gradient all-reduce: bf16 payload on the wire + error
    feedback keeps long-run drift bounded
  * context-parallel decode (cache length sharded) ≡ replicated decode
"""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devs(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_pjit_train_step_matches_single_device():
    run_devs("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core.collage import CollageAdamW
        from repro.core.precision import PrecisionPolicy, Strategy
        from repro.data.synthetic import make_batch_fn
        from repro.configs.base import ShapeConfig
        from repro.distributed import sharding as shard_lib
        from repro.models.model import build_model
        from repro.train import train_loop

        cfg = get_config("granite-3-2b", smoke=True)
        model = build_model(cfg)
        opt = CollageAdamW(1e-3, b2=0.95,
                           policy=PrecisionPolicy(strategy=Strategy.C_COLLAGE_PLUS))
        shape = ShapeConfig("t", 32, 8, "train")
        batch_fn = make_batch_fn(cfg, shape)
        step = train_loop.make_train_step(model, opt)

        # single-device reference
        state0 = train_loop.init_state(model, opt, jax.random.PRNGKey(0))
        sref, mref = jax.jit(step)(state0, batch_fn(0))

        # pjit on (data=2, model=4)
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        state_abs = jax.eval_shape(
            lambda: train_loop.init_state(model, opt, jax.random.PRNGKey(0)))
        st_sh = shard_lib.state_shardings(state_abs, mesh)
        b_sh = shard_lib.batch_shardings(jax.eval_shape(lambda: batch_fn(0)), mesh)
        with mesh:
            jstep = jax.jit(step, in_shardings=(st_sh, b_sh),
                            out_shardings=(st_sh, None))
            state = jax.device_put(state0, st_sh)
            batch = jax.device_put(batch_fn(0), b_sh)
            s2, m2 = jstep(state, batch)
        np.testing.assert_allclose(float(mref["loss"]), float(m2["loss"]),
                                   rtol=2e-2)
        # parameters must match elementwise (bf16-exact ops dominate)
        for a, b in zip(jax.tree_util.tree_leaves(sref.params),
                        jax.tree_util.tree_leaves(s2.params)):
            aa = np.asarray(a, np.float32); bb = np.asarray(b, np.float32)
            assert (np.abs(aa - bb) <= 2e-2 * np.maximum(np.abs(aa), 1)).mean() > 0.99
        print("PJIT_OK")
    """)


def test_pipeline_matches_sequential():
    run_devs("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import pipeline as pp

        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((4,), ("pod",))
        L, D, n_micro, mb = 8, 16, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        params = {"w": jax.random.normal(ks[0], (L, D, D), jnp.float32) * 0.1}
        x = jax.random.normal(ks[1], (n_micro, mb, D), jnp.float32)

        def layer(w, h):
            return jnp.tanh(h @ w)

        def stage_body(stage_params, h):
            def body(h, w):
                return layer(w, h), None
            h, _ = jax.lax.scan(body, h, stage_params["w"])
            return h

        def sequential(params, x):
            def body(h, w):
                return layer(w, h), None
            flat = x.reshape(n_micro * mb, D)
            h, _ = jax.lax.scan(body, flat, params["w"])
            return h.reshape(n_micro, mb, D)

        staged = pp.split_stages(params, 4)
        with mesh:
            got = pp.pipeline_apply(stage_body, staged, x, mesh=mesh, axis="pod")
        want = sequential(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

        # differentiability: d(loss)/d(params) matches
        def loss_pipe(staged):
            with mesh:
                o = pp.pipeline_apply(stage_body, staged, x, mesh=mesh, axis="pod")
            return jnp.sum(o ** 2)
        def loss_seq(params):
            return jnp.sum(sequential(params, x) ** 2)
        g_pipe = jax.grad(loss_pipe)(staged)["w"].reshape(L, D, D)
        g_seq = jax.grad(loss_seq)(params)["w"]
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                                   rtol=1e-4, atol=1e-4)
        sched = pp.make_schedule("gpipe", n_stages=4, n_micro=n_micro)
        print("PIPE_OK", float(sched.stats()["bubble_fraction"]))
    """)


# --------------------------------------------------------------------------
# schedule IR: host-side structural invariants (pure numpy — no devices)
# --------------------------------------------------------------------------

def _grid():
    from repro.distributed import pipeline as pp
    cases = []
    for S in (2, 4):
        for M in (4, 8):
            cases.append(pp.make_schedule("gpipe", n_stages=S, n_micro=M))
            cases.append(pp.make_schedule("1f1b", n_stages=S, n_micro=M))
            if M % S == 0:
                cases.append(pp.make_schedule(
                    "interleaved", n_stages=S, n_micro=M, n_virtual=2))
    return cases


def test_schedule_ir_op_coverage_and_dependencies():
    """Every (chunk, micro) runs its Fwd and Bwd exactly once; Fwd strictly
    precedes Bwd; every consumed value ARRIVED on an earlier tick (chunk
    dataflow and cotangent dataflow both ride the +1/−1 ring)."""
    import numpy as np
    for sched in _grid():
        S, M, C = sched.n_stages, sched.n_micro, sched.n_chunks
        fwd, bwd = {}, {}
        for t in range(sched.n_ticks):
            for s in range(S):
                if sched.f_chunk[t, s] >= 0:
                    c, m = int(sched.f_chunk[t, s]), int(sched.f_micro[t, s])
                    assert c % S == s, (sched.name, t, s, c)
                    fwd[(c, m)] = t
                if sched.b_chunk[t, s] >= 0:
                    c, m = int(sched.b_chunk[t, s]), int(sched.b_micro[t, s])
                    assert c % S == s, (sched.name, t, s, c)
                    bwd[(c, m)] = t
        want = {(c, m) for c in range(C) for m in range(M)}
        assert set(fwd) == want and set(bwd) == want, sched.name
        for c, m in want:
            assert fwd[(c, m)] < bwd[(c, m)], (sched.name, c, m)
            if c > 0:       # input activation arrived strictly earlier
                assert fwd[(c - 1, m)] < fwd[(c, m)], (sched.name, c, m)
            if c < C - 1:   # output cotangent arrived strictly earlier
                assert bwd[(c + 1, m)] < bwd[(c, m)], (sched.name, c, m)
        # slot indices in range wherever an op is scheduled
        assert (sched.f_slot < sched.n_fwd_slots).all()
        assert (sched.b_dyslot < sched.n_bwd_slots).all()
        assert np.all(sched.f_slot[sched.f_chunk > 0] >= 0)
        assert np.all(sched.b_dyslot[(sched.b_chunk >= 0)
                                     & (sched.b_chunk < C - 1)] >= 0)


def test_schedule_stash_slots_never_clobber_live_values():
    """Slot reuse is liveness-safe: between an activation's write (its
    producing arrival) and its last read (the Bwd recompute), no other
    value may be written into the same slot on the same device."""
    for sched in _grid():
        S, C = sched.n_stages, sched.n_chunks
        for s in range(S):
            live = {}   # slot -> (c, m, free_tick)
            for t in range(sched.n_ticks):
                # reads happen at the START of the tick
                if sched.b_chunk[t, s] > 0:
                    slot = int(sched.b_xslot[t, s])
                    c, m = int(sched.b_chunk[t, s]), int(sched.b_micro[t, s])
                    assert live.get(slot, (None,))[0] == (c, m), \
                        (sched.name, s, t, slot, live.get(slot))
                    del live[slot]
                # writes happen at the END of the tick
                w = int(sched.f_wslot[t, s])
                if w >= 0:
                    assert w not in live, (sched.name, s, t, w, live[w])
                    # find which op this arrival belongs to: the upstream
                    # device ran Fwd(c-1, m) this tick
                    up = (s - 1) % S
                    c = int(sched.f_chunk[t, up]) + 1
                    m = int(sched.f_micro[t, up])
                    live[w] = ((c, m), t)
            assert not live, (sched.name, s, live)


def test_schedule_bubble_ordering_and_stash_economy():
    """The structural claims the cost-model gate reuses: under the
    masked-tick execution model 1F1B and interleaved both beat GPipe on
    bubble fraction at equal (S, M), and 1F1B's activation stash is the
    classic min(M, S) bound instead of GPipe's M."""
    from repro.distributed import pipeline as pp
    for S, M in ((2, 4), (4, 8)):
        g = pp.make_schedule("gpipe", n_stages=S, n_micro=M).stats()
        o = pp.make_schedule("1f1b", n_stages=S, n_micro=M).stats()
        assert o["bubble_fraction"] < g["bubble_fraction"], (S, M, o, g)
        assert o["n_fwd_slots"] == min(M, S) < g["n_fwd_slots"] == M, (o, g)
        if M % S == 0:
            v = pp.make_schedule("interleaved", n_stages=S, n_micro=M,
                                 n_virtual=2).stats()
            assert v["bubble_fraction"] < g["bubble_fraction"], (S, M, v, g)


def test_schedule_comm_ready_ordering():
    """Bucket classes close in head ≤ embed ≤ stage order (the head grad
    needs only final-chunk Bwds; embed needs every chunk-0 Bwd; the stage
    class closes with the overall last Bwd) — this order drives the
    collective launch sequence in the engine and the overlap model."""
    for sched in _grid():
        r = sched.comm_ready
        assert r["head"] <= r["embed"] <= r["stage"] <= sched.n_ticks, \
            (sched.name, r)


def test_schedule_validation_errors():
    import pytest
    from repro.distributed import pipeline as pp
    with pytest.raises(ValueError, match="unknown schedule"):
        pp.make_schedule("zb-h1", n_stages=2, n_micro=4)
    with pytest.raises(ValueError, match="interleaved"):
        pp.make_schedule("gpipe", n_stages=2, n_micro=4, n_virtual=2)
    with pytest.raises(ValueError, match="n_virtual >= 2"):
        pp.make_schedule("interleaved", n_stages=2, n_micro=4, n_virtual=1)
    with pytest.raises(ValueError, match="n_micro % n_stages"):
        pp.make_schedule("interleaved", n_stages=4, n_micro=6, n_virtual=2)


def test_grad_compression_wire_dtype_and_error_feedback():
    run_devs("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression
        from repro.utils import hlo_analysis

        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((8,), ("data",))

        def compressed_psum(g, err):
            return compression.pmean_compressed(g, err, jnp.bfloat16,
                                                "data", 8)

        f = jax.shard_map(compressed_psum, mesh=mesh,
                          in_specs=(P("data"), P("data")),
                          out_specs=(P("data"), P("data")), check_vma=False)
        g = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
        err = jnp.zeros((64, 128), jnp.float32)
        # check the backend-neutral IR: the CPU *backend* upcasts bf16
        # collectives to f32 (an artifact the roofline analyzer corrects);
        # on TPU the wire payload stays bf16 as staged out here.
        txt = jax.jit(f).lower(g, err).as_text()
        census = hlo_analysis.collective_dtype_census(txt)
        assert census.get("all_reduce") == {"bf16": 1}, census

        # error feedback: accumulated compressed-mean ≈ true mean over steps
        true_acc = jnp.zeros((64, 128), jnp.float32)
        comp_acc = jnp.zeros((64, 128), jnp.float32)
        err = None
        for i in range(50):
            g = jax.random.normal(jax.random.PRNGKey(i), (64, 128), jnp.float32) * 1e-3
            q, err = compression.compress_decompress(g, err, jnp.bfloat16)
            comp_acc = comp_acc + q
            true_acc = true_acc + g
        resid = np.abs(np.asarray(comp_acc + err.astype(jnp.float32) - true_acc))
        # with EF the drift stays O(one rounding), not O(steps·rounding)
        assert resid.max() < 5e-5, resid.max()
        print("COMP_OK")
    """)


def test_context_parallel_decode_matches():
    run_devs("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as shard_lib
        from repro.models.model import build_model

        cfg = get_config("granite-3-2b", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, L = 1, 64
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0,
                                              cfg.vocab_size)}
        _, state = model.prefill(params, batch, cache_len=L)
        tok = jnp.ones((B, 1), jnp.int32)
        ref, _ = model.decode_step(params, state, tok)

        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((4, 2), ("data", "model"))
        with mesh:
            p_sh = shard_lib.state_shardings(
                jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))), mesh)
            s_sh = shard_lib.cache_shardings(
                jax.eval_shape(lambda: state), mesh, context_parallel=True)
            pd = jax.device_put(params, p_sh)
            sd = jax.device_put(state, s_sh)
            got, _ = jax.jit(model.decode_step)(pd, sd, tok)
        np.testing.assert_allclose(np.asarray(ref, np.float32),
                                   np.asarray(got, np.float32),
                                   rtol=3e-2, atol=3e-2)
        print("CTX_OK")
    """)


def test_generation_engine_lowers_on_tp_mesh():
    """The jit-resident generate (prefill + scan decode loop, donated
    DecodeState) must lower and compile under FSDP×TP shardings."""
    run_devs("""
        import functools
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed import sharding as shard_lib
        from repro.models.model import build_model

        cfg = get_config("granite-3-2b", smoke=True)
        model = build_model(cfg)
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4, 2), ("data", "model"))
        params_abs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        p_sh = shard_lib.state_shardings(params_abs, mesh)
        batch_abs = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        b_sh = shard_lib.batch_shardings(batch_abs, mesh)
        with mesh:
            # one-step decode with donated state: cache buffers must alias
            state_abs = jax.eval_shape(
                lambda: model.init_decode_state(8, 32))
            s_sh = shard_lib.cache_shardings(state_abs, mesh)
            tok_abs = jax.ShapeDtypeStruct((8, 1), jnp.int32)
            step = jax.jit(model.decode_step,
                           in_shardings=(p_sh, s_sh, None),
                           out_shardings=(None, s_sh), donate_argnums=(1,))
            cstep = step.lower(params_abs, state_abs, tok_abs).compile()
            assert cstep.memory_analysis().alias_size_in_bytes > 0

            # whole generation loop in one program
            gen = jax.jit(functools.partial(model.generate, max_new_tokens=8),
                          in_shardings=(p_sh, b_sh))
            gen.lower(params_abs, batch_abs).compile()
        print("ENGINE_TP_OK")
    """)
