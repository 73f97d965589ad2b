"""Runtime substrates: data determinism, optimizers, compression,
straggler monitor, elastic replanning, policy cost model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import CostModel, OpShape, calibrate, decide_rc_clc
from repro.data import DataConfig, host_batch
from repro.optim import (OptConfig, apply_updates, clip_by_global_norm,
                         init_opt_state)
from repro.runtime.elastic import replan_mesh, rescale_batch
from repro.runtime.straggler import StragglerMonitor, StragglerPolicy


def test_data_deterministic_and_host_disjoint():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=8)
    t1, l1 = host_batch(cfg, 5)
    t2, l2 = host_batch(cfg, 5)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    # labels are the shifted stream
    np.testing.assert_array_equal(np.asarray(t1[:, 1:]),
                                  np.asarray(l1[:, :-1]))
    # two hosts see disjoint example indices covering the global batch
    a, _ = host_batch(cfg, 5, host_id=0, num_hosts=2)
    b, _ = host_batch(cfg, 5, host_id=1, num_hosts=2)
    assert a.shape[0] == 4 and b.shape[0] == 4
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(t1),
                                  np.concatenate([a, b], axis=0))


@pytest.mark.parametrize("kind", ["adamw", "adafactor"])
def test_optimizer_reduces_quadratic(kind):
    params = {"w": jnp.array([3.0, -2.0, 1.5])}
    cfg = OptConfig(kind=kind, lr=0.1, weight_decay=0.0)
    state = init_opt_state(params, cfg)
    for _ in range(60):
        grads = jax.tree.map(lambda p: 2 * p, params)   # d/dp p^2
        grads, _ = clip_by_global_norm(grads, 10.0)
        params, state = apply_updates(params, grads, state, cfg,
                                      jnp.float32(0.05))
    assert float(jnp.sum(params["w"] ** 2)) < 0.5


def test_adafactor_state_is_factored():
    params = {"big": jnp.zeros((256, 512)), "small": jnp.zeros((4, 8))}
    st = init_opt_state(params, OptConfig(kind="adafactor"))
    assert set(st["v"]["big"].keys()) == {"r", "c"}
    assert st["v"]["big"]["r"].shape == (256,)
    assert st["v"]["big"]["c"].shape == (512,)
    assert set(st["v"]["small"].keys()) == {"v"}


def test_compression_error_feedback_converges():
    """Error feedback bounds the running deviation by one quantum: after N
    steps |mean(emitted) - g| <= quantum/N, even for grads far below the
    quantisation step (they'd be silently zeroed without feedback)."""
    from repro.optim.compression import compress, decompress
    g = jnp.array([1e-4, 2e-4, -5e-5, 1.0])  # tiny grads next to a big one
    err = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    steps = 512
    quantum = float(jnp.max(jnp.abs(g))) / 127.0
    for _ in range(steps):
        q, s, err = compress(g, err)
        acc = acc + decompress(q, s)
    np.testing.assert_allclose(np.asarray(acc / steps), np.asarray(g),
                               atol=1.1 * quantum / steps)
    # without feedback the sub-quantum grads are lost entirely
    q0, s0, _ = compress(g, jnp.zeros_like(g))
    assert float(decompress(q0, s0)[2]) == 0.0


def test_compressed_allreduce_exact_with_shared_scale():
    from repro.optim.compression import allreduce_compressed
    devs = jax.local_device_count()
    if devs < 1:
        pytest.skip("no devices")
    g = jnp.stack([jnp.array([1.0, -2.0, 0.5])] * devs)
    err = jnp.zeros_like(g)
    out, _ = jax.pmap(lambda g, e: allreduce_compressed(g, e, "i"),
                      axis_name="i")(g, err)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(g[0]), rtol=0.02)


def test_straggler_monitor_flags_slow_host():
    mon = StragglerMonitor(StragglerPolicy(min_samples=4))
    for _ in range(10):
        mon.record(1.0, host_id=0)
        mon.record(1.05, host_id=1)
        mon.record(3.5, host_id=2)   # straggler
    assert mon.check_hosts() == [2]
    assert mon.deadline() > 3.0  # deadline = 3x median(~1.05)


def test_elastic_replan_and_rescale():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError):
        replan_mesh(mesh, lost_hosts=1)
    assert rescale_batch(256, 16, 8) == 32


def test_policy_matches_paper_regimes():
    """Paper SS4.3: early conv layers (big fmap, small kernels) enable RC;
    late layers (small fmap, many kernels) tend to disable it."""
    early = OpShape(n=64, m=32, ch=3, r=11, h=55)      # alexnet conv1-ish
    late = OpShape(n=64, m=1024, ch=1024, r=3, h=13)   # yolo conv18-ish
    rc_e, _ = decide_rc_clc(early)
    rc_l, _ = decide_rc_clc(late)
    assert rc_e or rc_l  # at least one regime enables
    # and the decision is not constant across regimes for RC or ClC
    assert (rc_e != rc_l) or (decide_rc_clc(early)[1] !=
                              decide_rc_clc(late)[1])


def test_policy_calibration_recovers_coefficients():
    true = CostModel(alpha=2e-9, beta=5e-10)
    shapes = [OpShape(n=b, m=m, ch=c, r=3, h=h)
              for b, m, c, h in [(64, 96, 3, 55), (32, 256, 96, 27),
                                 (64, 384, 256, 13), (16, 512, 512, 7)]]
    samples = []
    for s in shapes:
        samples += [(s, "fc", true.t_fc(s)), (s, "rc", true.t_rc(s)),
                    (s, "clc", true.t_clc(s)), (s, "coc", true.t_coc(s))]
    fit = calibrate(samples)
    assert abs(fit.alpha - true.alpha) / true.alpha < 0.05
    assert abs(fit.beta - true.beta) / true.beta < 0.05


def test_constraint_helpers_noop_only_without_a_mesh():
    """The sharding-constraint helpers return their input untouched when
    no mesh is in scope, constrain under `jax.set_mesh`, and let any
    other error of the constraint propagate."""
    from repro.core.protected import _replicate_small
    from repro.runtime.sharding import make_mesh, maybe_constrain
    x = jnp.arange(8.0).reshape(2, 4)
    assert jax.sharding.get_abstract_mesh().empty
    assert maybe_constrain(x, "data", None) is x
    assert _replicate_small(x) is x
    mesh = make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        assert not jax.sharding.get_abstract_mesh().empty
        y = jax.jit(lambda a: maybe_constrain(a, "data", None))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        z = jax.jit(_replicate_small)(x)
        np.testing.assert_array_equal(np.asarray(z), np.asarray(x))
        with pytest.raises(Exception):
            jax.jit(lambda a: maybe_constrain(a, "no_such_axis"))(x)


def test_compile_cache_dir_follows_env_else_repo(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX (no other
    directory configured); otherwise the fixed <repo>/.jax_cache."""
    import os

    from repro.launch import compile_cache as CC
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CC.ENV, "/somewhere/else")
        assert CC.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv(CC.ENV)
        got = CC.enable_compile_cache()
        repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                            ".."))
        assert got == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
