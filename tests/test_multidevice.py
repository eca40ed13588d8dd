"""Real multi-device SPMD execution (8 CPU devices in a subprocess):
sharded train/decode must match single-device numerics.  This is the
strongest correctness evidence for the sharding rules — not just that the
partitioned program compiles, but that it computes the same thing."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config, smoke_variant
    from repro.launch import sharding as sh
    from repro.models import Batch, build_model
    from repro.training.optimizer import OptimizerConfig, init_opt_state
    from repro.training.train_loop import TrainState, make_train_step

    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=2, d_model=128,
                        vocab=512)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, init_opt_state(params))
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = make_train_step(model, ocfg, remat=True)

    rng = np.random.default_rng(0)
    batch = Batch(tokens=jnp.asarray(rng.integers(0, 512, (8, 32)), jnp.int32),
                  loss_mask=jnp.ones((8, 32)))

    # single-device reference
    s_ref, m_ref = jax.jit(step)(state, batch)
    loss_ref = float(m_ref["loss"])

    # 2x4 (data, model) mesh with the production sharding rules
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    p_sh = sh.param_shardings(mesh, params)
    from repro.training.optimizer import OptState
    repl = NamedSharding(mesh, P())
    opt_sh = OptState(step=repl, mu=sh.param_shardings(mesh, state.opt.mu),
                      nu=sh.param_shardings(mesh, state.opt.nu))
    b_sh = Batch(tokens=NamedSharding(mesh, P("data", None)),
                 loss_mask=NamedSharding(mesh, P("data", None)))
    with jax.set_mesh(mesh):
        f = jax.jit(step, in_shardings=(TrainState(p_sh, opt_sh), b_sh))
        s_sp, m_sp = f(state, batch)
    loss_sp = float(m_sp["loss"])

    # compare a few updated param leaves
    la = jax.tree_util.tree_leaves(s_ref.params)
    lb = jax.tree_util.tree_leaves(s_sp.params)
    max_diff = max(float(jnp.max(jnp.abs(a - jax.device_get(b))))
                   for a, b in zip(la, lb))
    print(json.dumps({"loss_ref": loss_ref, "loss_sp": loss_sp,
                      "max_param_diff": max_diff}))
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss_ref"] - res["loss_sp"]) < 1e-4, res
    assert res["max_param_diff"] < 5e-4, res
