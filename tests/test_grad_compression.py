"""INT8 gradient all-reduce with error feedback (multi-device via subprocess:
the suite runs with 1 CPU device; the compression path needs ≥4)."""

import textwrap

from subproc import run_script

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.optim.grad_compression import (
        compress_decompress_psum, init_error_buf)

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    local = rng.standard_normal((8, 64, 32)).astype(np.float32)
    grads = {"w": jnp.asarray(local)}
    err0 = {"w": jnp.zeros((8, 64, 32), jnp.float32)}

    def f(g, e):
        g = {"w": g["w"][0]}
        e = {"w": e["w"][0]}
        mean, new_e = compress_decompress_psum(g, e, ("data",))
        return {"w": mean["w"][None]}, {"w": new_e["w"][None]}

    fm = shard_map(f, mesh=mesh,
                   in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")))
    mean, err = fm(grads, err0)
    true_mean = local.mean(0)
    got = np.asarray(mean["w"][0])
    rel = np.abs(got - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
    print("REL", rel)
    assert rel < 0.05, rel

    # error feedback: two rounds of the same gradient — accumulated result
    # converges toward the exact mean (residual is re-injected)
    mean2, err2 = fm(grads, err)
    got2 = (np.asarray(mean["w"][0]) + np.asarray(mean2["w"][0])) / 2
    rel2 = np.abs(got2 - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
    print("REL2", rel2)
    assert rel2 < rel * 1.05
    print("OK")
""")


def test_compressed_allreduce_subprocess():
    run_script(SCRIPT, timeout=300)


def test_compressed_train_step_subprocess():
    """Full compressed-DP training step on an 8-device host mesh: loss
    decreases over a few steps with int8 gradient exchange."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.models.config import ModelConfig
        from repro.models import registry, schema as schema_lib
        from repro.optim import optimizer as opt_lib
        from repro.optim.optimizer import OptConfig
        from repro.train.trainer import TrainConfig, make_compressed_train_step
        from repro.data.pipeline import DataConfig, batch_for_step
        import jax.numpy as jnp

        model = ModelConfig(name="c", family="dense", n_layers=2, d_model=64,
                            n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                            attn_chunk_q=16, max_seq=64)
        mesh = make_host_mesh(model=1)
        tc = TrainConfig(model=model, opt=OptConfig(lr=3e-3, warmup_steps=2,
                                                    total_steps=20),
                         global_batch=8, seq_len=32, dp_compress=True)
        arch = registry.build(model)
        params = schema_lib.init_params(arch.schema(), jax.random.key(0))
        opt_state = opt_lib.init(tc.opt, params)
        step, init_err = make_compressed_train_step(arch, tc, mesh)
        err = init_err(params)
        dcfg = DataConfig(vocab=128, seq_len=32, global_batch=8)
        losses = []
        with mesh:
            jstep = jax.jit(step)
            for i in range(12):
                toks = jnp.asarray(batch_for_step(dcfg, i))
                params, opt_state, err, m = jstep(params, opt_state, err, toks)
                losses.append(float(m["loss"]))
        print("L0", losses[0], "LN", losses[-1])
        assert losses[-1] < losses[0], losses
        print("OK")
    """)
    run_script(script, timeout=560)
