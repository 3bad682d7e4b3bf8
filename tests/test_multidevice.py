"""Multi-device integration (subprocess with 8 host devices): sharded train
step on the production sharding plan, compressed-DP step, and a smoke of the
dry-run cell builder.  Kept in subprocesses so the main test process stays on
the default 1-device backend."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config, make_tiny, TrainConfig
    from repro.models import init_params
    from repro.optim import adamw_init
    from repro.train import make_train_step
    from repro.parallel.sharding import make_plan, param_shardings, make_sharder

    cfg = make_tiny(get_config("qwen2.5-3b"))
    tcfg = TrainConfig(total_steps=10, warmup_steps=2)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt = adamw_init(params)
    batch = {"tokens": jax.random.randint(key, (8, 64), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab_size)}

    # single device reference
    step0 = jax.jit(make_train_step(cfg, tcfg))
    p_ref, _, m_ref = step0(params, opt, batch, jnp.int32(0))

    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    plan = make_plan(cfg, mesh)
    sh = make_sharder(cfg, mesh, plan, "train", 8)
    pspecs = param_shardings(cfg, mesh, plan)
    named = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                   is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    o_sh = {"m": named, "v": named, "count": rep}
    bspec = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    step = jax.jit(make_train_step(cfg, tcfg, sh=sh, grad_shardings=named),
                   in_shardings=(named, o_sh, bspec, rep),
                   out_shardings=(named, o_sh, rep))
    p_sh, _, m_sh = step(params, opt, batch, jnp.int32(0))
    # loss identical up to bf16/reduction noise
    assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 0.05, (m_ref, m_sh)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-2)
    print("sharded step OK")
    """)


@pytest.mark.slow
def test_compressed_dp_step_close_to_exact():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.configs import get_config, make_tiny, TrainConfig
    from repro.models import init_params
    from repro.optim import adamw_init
    from repro.train import make_train_step
    from repro.train.loop import make_dp_train_step

    cfg = make_tiny(get_config("qwen2.5-3b"))
    tcfg = TrainConfig(total_steps=10, warmup_steps=2)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (8, 64), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab_size)}

    mesh = jax.make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
    step, init_fn = make_dp_train_step(cfg, tcfg, mesh, dp_axis="pod")
    opt = init_fn(params)
    p1, o1, m1 = step(params, opt, batch, jnp.int32(0))

    ref = jax.jit(make_train_step(cfg, tcfg))
    p_ref, _, m_ref = ref(params, adamw_init(params), batch, jnp.int32(0))
    assert abs(float(m1["loss"]) - float(m_ref["loss"])) < 0.05
    # int8-compressed grads: params close but not identical
    deltas = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
              for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p_ref))]
    assert max(deltas) < 5e-2, max(deltas)
    print("compressed DP OK")
    """)


@pytest.mark.slow
def test_decode_cell_builder_smoke():
    _run("""
    import jax
    from jax.sharding import AxisType
    from repro.configs import get_config, make_tiny
    from repro.configs.base import ShapeConfig
    from repro.launch.dryrun import build_cell
    cfg = make_tiny(get_config("gemma3-4b"))
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
    for shape in [ShapeConfig("train", "train", 64, 8), ShapeConfig("decode", "decode", 128, 8)]:
        fn, args = build_cell(cfg, shape, mesh)
        fn.lower(*args).compile()
    print("cell builder OK")
    """)


@pytest.mark.slow
def test_pipeline_parallel_parity():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from jax.sharding import PartitionSpec as P
    from repro.parallel.pipeline import make_pipelined_fn

    S, M, mb, D = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, D, D)) / np.sqrt(D)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))
    mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
    piped = make_pipelined_fn(stage_fn, mesh, S)
    out_p = piped(ws, x)

    # sequential reference
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ ws[s])
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref), atol=1e-5)

    # differentiable end-to-end
    def loss(ws):
        return (piped(ws, x) ** 2).mean()
    g = jax.grad(loss)(ws)
    assert np.isfinite(np.asarray(g)).all() and np.abs(np.asarray(g)).sum() > 0
    print("pipeline parity OK")
    """)
