"""Distributed correctness on emulated host devices (subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8): sharded train step
matches the single-device reference, and the sharding rules are legal on
a real (data, model) mesh."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, %r)
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.configs as C
    from repro.launch.steps import init_train_state, make_train_step
    from repro.optim import OptConfig
    from repro.runtime import sharding as SH
    from repro.data import DataConfig, host_batch

    assert jax.device_count() == 8, jax.device_count()
    cfg = C.reduced(C.get("yi-9b")).replace(num_layers=2, remat=False)
    opt = OptConfig(lr=1e-3)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    tokens, labels = host_batch(dcfg, 0)
    batch = {"tokens": tokens, "labels": labels}

    # single-device reference
    state0 = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step0 = jax.jit(make_train_step(cfg, opt))
    ref_state, ref_m = step0(state0, batch)

    # sharded: (data=4, model=2)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(4, 2)
    with jax.set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        psh = SH.param_shardings(state["params"], mesh, cfg)
        osh = SH.param_shardings(state["opt"], mesh, cfg)
        state = {"params": jax.tree.map(jax.device_put, state["params"], psh),
                 "opt": jax.tree.map(jax.device_put, state["opt"], osh),
                 "step": state["step"]}
        bspec = NamedSharding(mesh, P("data", None))
        sbatch = jax.tree.map(lambda x: jax.device_put(x, bspec), batch)
        step = jax.jit(make_train_step(cfg, opt, microbatches=2,
                                       mesh_axes=("data", "model")))
        new_state, m = step(state, sbatch)

    loss_ref = float(ref_m["loss"])
    loss_sh = float(m["loss"])
    # compare a few parameter leaves after the step
    ref_leaves = jax.tree.leaves(ref_state["params"])
    sh_leaves = jax.tree.leaves(new_state["params"])
    max_err = max(float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - jax.device_get(b).astype(jnp.float32))))
        for a, b in zip(ref_leaves, sh_leaves))
    print(json.dumps({"loss_ref": loss_ref, "loss_sharded": loss_sh,
                      "param_max_err": max_err}))
""")


@pytest.mark.slow
def test_sharded_step_matches_single_device():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT % (os.path.abspath(src),)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    # microbatch split changes reduction order; tolerance is fp-level
    assert abs(data["loss_ref"] - data["loss_sharded"]) < 2e-2, data
    assert data["param_max_err"] < 2e-2, data
