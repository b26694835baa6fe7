"""Real multi-device execution: an 8-device pjit train step with our
sharding rules, run in a subprocess (device count must be set before jax
init), plus checkpoint resharding."""
import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    AUTO = (AxisType.Auto,) * 2
    import numpy as np
    from repro.configs import base
    from repro.distributed import sharding
    from repro.models.lm import build_model
    from repro.training import optimizer as opt_lib, checkpoint as ckpt_lib
    from repro.training.train_step import make_train_step
    import tempfile

    cfg = base.get_config("h2o-danube-1.8b").reduced()
    # widen dims so a (4, 2) mesh divides them
    import dataclasses
    cfg = dataclasses.replace(cfg, d_model=64, d_ff=128, vocab=512)
    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO)
    model = build_model(cfg)
    adamw = opt_lib.AdamWConfig(lr=1e-3)
    step = make_train_step(cfg, model, adamw, block_q=16)
    params = model.init(jax.random.PRNGKey(0))
    opt = opt_lib.init_state(params)
    p_sh = sharding.param_shardings(cfg, params, mesh, train=True)
    o_sh = {"mu": p_sh, "nu": p_sh,
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
    params = jax.tree.map(jax.device_put, params, p_sh)
    opt = {"mu": jax.tree.map(jax.device_put, opt["mu"], p_sh),
           "nu": jax.tree.map(jax.device_put, opt["nu"], p_sh),
           "step": opt["step"]}
    fn = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                 out_shardings=(p_sh, o_sh, None))
    B, S = 8, 32
    batch = {"tokens": jnp.zeros((B, S), jnp.int32) + 3,
             "labels": jnp.ones((B, S), jnp.int32)}
    params2, opt2, m = fn(params, opt, batch)
    loss1 = float(m["loss"])

    # checkpoint on (4,2), restore resharded onto (2,4) — elastic re-mesh
    with tempfile.TemporaryDirectory() as d:
        ckpt_lib.save(d, 1, params2)
        mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=AUTO)
        p_sh2 = sharding.param_shardings(cfg, params2, mesh2, train=True)
        restored, _ = ckpt_lib.restore(d, 1, params2, shardings=p_sh2)
        same = all(np.allclose(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(params2),
                                   jax.tree.leaves(restored)))
    print(json.dumps({"loss": loss1, "reshard_ok": bool(same),
                      "n_dev": jax.device_count()}))
""")


def test_8dev_train_step_and_reshard():
    env = {**os.environ, "PYTHONPATH": "src"}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_dev"] == 8
    assert out["reshard_ok"]
    assert out["loss"] > 0
