"""Distribution tests that need multiple devices: run in subprocesses with
``--xla_force_host_platform_device_count`` so the main test process keeps
its single-device view (per the dry-run isolation rule)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path


ROOT = Path(__file__).resolve().parents[1]


def _run(script: str, n_devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count"
               f"={n_devices}").strip()
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_pipeline_parallel_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.train.pipeline import (pipeline_apply, split_microbatches,
                                      bubble_fraction)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    S, M, B, D = 4, 8, 16, 32
    rng = jax.random.PRNGKey(0)
    ws = jax.random.normal(rng, (S, D, D)) * 0.3
    params = {"w": ws}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    x = jax.random.normal(jax.random.fold_in(rng, 1), (M * B, D))
    xs = split_microbatches(x, M)
    with mesh:
        out = pipeline_apply(stage_fn, params, xs, mesh=mesh, axis="stage")
    out = np.asarray(out.reshape(M * B, D))

    ref = x
    for i in range(S):
        ref = jnp.tanh(ref @ ws[i])
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
    print("pipeline fwd OK")
    """)


def test_pipeline_parallel_gradients():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.train.pipeline import pipeline_loss, split_microbatches
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    S, M, B, D = 4, 4, 8, 16
    rng = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(rng, (S, D, D)) * 0.3}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    x = jax.random.normal(jax.random.fold_in(rng, 1), (M * B, D))
    t = jax.random.normal(jax.random.fold_in(rng, 2), (M * B, D))
    xs, ts = split_microbatches(x, M), split_microbatches(t, M)

    with mesh:
        gp = jax.grad(lambda p: pipeline_loss(
            stage_fn, loss_fn, p, xs, ts, mesh=mesh, axis="stage"))(params)

    def seq_loss(p):
        y = x
        for i in range(S):
            y = jnp.tanh(y @ p["w"][i])
        return jnp.mean(jax.vmap(loss_fn)(
            y.reshape(M, B, D), t.reshape(M, B, D)))

    gs = jax.grad(seq_loss)(params)
    np.testing.assert_allclose(np.asarray(gp["w"]), np.asarray(gs["w"]),
                               rtol=1e-4, atol=1e-5)
    print("pipeline grad OK")
    """)


def test_train_step_lowers_on_small_mesh():
    """Reduced arch through the real StepBundle machinery on a 4x2 mesh."""
    _run("""
    import jax
    from repro.configs import ARCHS, SHAPES
    from repro.configs.base import ShapeConfig
    from repro.models.model import build_model, reduce_config
    from repro.optim import make_optimizer
    from repro.train.step import build_step, lower_step
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = reduce_config(ARCHS["llama3.2-3b"], d_model=64, n_heads=4,
                        n_kv_heads=2, vocab=512)
    model = build_model(cfg)
    shape = ShapeConfig("t", 64, 8, "train")
    bundle = build_step(model, make_optimizer("adamw"), mesh, shape,
                        microbatches=2)
    compiled = lower_step(bundle).compile()
    assert compiled.cost_analysis() is not None
    print("train lower OK")
    """)


def test_train_step_executes_on_small_mesh():
    """Actually run two sharded train steps and check loss decreases."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS
    from repro.configs.base import ShapeConfig
    from repro.models.model import build_model, reduce_config
    from repro.optim import make_optimizer
    from repro.train.step import make_train_step
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = reduce_config(ARCHS["granite-moe-1b-a400m"], d_model=64,
                        n_heads=4, n_kv_heads=2, vocab=512)
    model = build_model(cfg)
    shape = ShapeConfig("t", 32, 8, "train")
    bundle = make_train_step(model, make_optimizer("adamw", lr=3e-3), mesh,
                             shape)
    step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                   out_shardings=bundle.out_shardings,
                   donate_argnums=bundle.donate_argnums)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = make_optimizer("adamw", lr=3e-3).init(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 512, (8, 32)), jnp.int32),
             "targets": jnp.asarray(rng.integers(0, 512, (8, 32)), jnp.int32)}
    with mesh:
        losses = []
        for _ in range(8):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    print("train exec OK", losses[0], "->", losses[-1])
    """)


def test_decode_step_lowers_on_small_mesh():
    _run("""
    import jax
    from repro.configs import ARCHS
    from repro.configs.base import ShapeConfig
    from repro.models.model import build_model, reduce_config
    from repro.train.step import build_step, lower_step
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    for arch in ("zamba2-7b", "xlstm-1.3b", "phi4-mini-3.8b"):
        cfg = reduce_config(ARCHS[arch], d_model=64, vocab=512)
        model = build_model(cfg)
        shape = ShapeConfig("d", 128, 8, "decode")
        bundle = build_step(model, None, mesh, shape)
        lower_step(bundle).compile()
        print(arch, "decode lower OK")
    """)


def test_compressed_pod_allreduce():
    """EF-int8 cross-pod gradient reduction inside shard_map."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import compressed_psum_pod, init_residual
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("pod", "data"))
    g_global = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
    grads = {"w": g_global}
    res = {"w": jnp.zeros((64, 32))}

    @partial(shard_map, mesh=mesh, in_specs=(P("pod"), P()), out_specs=P("pod"),
             check_rep=False)
    def reduce(g, r):
        local = {"w": g[0]}
        mean, new_res = compressed_psum_pod(local, {"w": r}, axis_name="pod")
        return mean["w"][None]

    out = reduce(g_global, res["w"])
    true_mean = np.asarray(g_global.mean(axis=0))
    got = np.asarray(out[0])
    scale = np.abs(true_mean).max()
    assert np.abs(got - true_mean).max() < scale * 0.05, "int8 mean too far"
    print("compressed pod all-reduce OK")
    """, n_devices=8)
