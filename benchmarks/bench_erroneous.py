"""Fig. 10(b)/(c) + Fig. 11: erroneous-case overhead with the paper's
injection protocol (one corrupted conv layer per epoch, L epochs), with
RC/ClC disabled vs layerwise-optimised, plus the distribution of which
scheme corrected each fault.

Injection goes through the campaign fault-model registry (the paper's
SS6.1 "burst" model: up to 100 elements in one random row/column) and the
per-layer verdicts aggregate through the same scheme_histogram the
campaign tables use - so this bench and `python -m repro.campaign.run`
report faults in the same vocabulary.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (FAULT_MODELS, ProtectionPlan, build_plan,
                        scheme_histogram)
from repro.core import injection as inj
from repro.models import cnn
from .common import row, time_fn

SCALE = 0.12
IMG = 64
BATCH = 8
FAULT_MODEL = "burst"     # paper SS6.1: random row OR column burst


def _run_model(name: str, layerwise: bool):
    cfg = cnn.CNN_REGISTRY[name](SCALE)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": IMG})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, 3, IMG, IMG),
                          jnp.float32)
    plan = build_plan(params, cfg, batch=BATCH)
    if not layerwise:
        # Fig. 10b variant: same plan, RC/ClC forced off everywhere
        plan = ProtectionPlan(
            entries={n: dataclasses.replace(
                e, cfg=e.cfg.replace(rc_enabled=False, clc_enabled=False))
                for n, e in plan.entries.items()},
            meta=dict(plan.meta))
    off = cfg.__class__(**{**cfg.__dict__, "abft": False})
    f_plain = jax.jit(lambda p, x: cnn.forward_cnn(p, x, off)[0])
    t_plain = time_fn(f_plain, params, x)

    # the paper's protocol is L epochs (one injection per conv layer); on
    # the 1-core container we sample <=5 evenly-spaced layers per model
    model = FAULT_MODELS[FAULT_MODEL]
    L = len(cfg.convs)
    layers = list(range(0, L, max(L // 5, 1)))[:5]
    total = 0.0
    corrected = []
    for layer in layers:
        _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
        n, m = o_clean.shape[0], o_clean.shape[1]
        p = o_clean.shape[2] * o_clean.shape[3]
        spec = model.plan(jax.random.PRNGKey(layer * 31 + 5), n, m, p,
                          max_elems=100)
        o_bad = inj.inject(o_clean, spec, model)
        f = jax.jit(lambda p_, x_, o_: cnn.forward_cnn(
            p_, x_, cfg, plan=plan, inject_layer=layer,
            inject_o={layer: o_}))
        logits, rep = f(params, x, o_bad)
        total += time_fn(f, params, x, o_bad)
        corrected.append(int(rep.corrected_by))
        assert int(rep.residual) == 0, (name, layer)
    avg = total / len(layers)
    ovh = (avg - t_plain) / t_plain * 100
    return avg, ovh, scheme_histogram(np.array(corrected))


def run(models=("alexnet", "resnet18")):
    out = []
    print("# Fig10b: erroneous overhead, RC/ClC disabled")
    for name in models:
        avg, ovh, dist = _run_model(name, layerwise=False)
        out.append(row(f"fig10b/{name}", avg * 1e6,
                       f"overhead_pct={ovh:.2f};corrected={dist}"))
    print("# Fig10c/Fig11: erroneous overhead, layerwise RC/ClC")
    for name in models:
        avg, ovh, dist = _run_model(name, layerwise=True)
        out.append(row(f"fig10c/{name}", avg * 1e6,
                       f"overhead_pct={ovh:.2f};corrected={dist}"))
    return out


if __name__ == "__main__":
    run()
