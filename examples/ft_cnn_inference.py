"""Paper-faithful example (FT-Caffe workflow): resilient CNN inference
under per-layer soft-error injection - the paper's SS6 protocol on
AlexNet/ResNet-18/YOLOv2 with the two-phase ProtectionPlan flow: the plan
is compiled offline (layerwise RC/ClC policy + precomputed weight
checksums), then every online forward just takes it.

    PYTHONPATH=src python examples/ft_cnn_inference.py --model resnet18
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import SCHEME_NAMES, build_plan  # noqa: E402
from repro.core import injection as inj  # noqa: E402
from repro.models import cnn  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="alexnet",
                    choices=sorted(cnn.CNN_REGISTRY))
    ap.add_argument("--scale", type=float, default=0.12)
    ap.add_argument("--img", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()

    cfg = cnn.CNN_REGISTRY[args.model](args.scale)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": args.img})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.batch, 3, args.img, args.img))
    # offline phase: one plan per model - per-layer RC/ClC decisions and
    # precomputed weight checksums (serializable: plan.save("plan.json"))
    plan = build_plan(params, cfg, batch=args.batch)
    convs = [e for e in plan.entries.values() if e.op.kind == "conv"]
    print(f"{args.model}: {len(cfg.convs)} conv layers; layerwise policy "
          f"RC on {sum(e.cfg.rc_enabled for e in convs)}, "
          f"ClC on {sum(e.cfg.clc_enabled for e in convs)} layers")

    clean, _ = cnn.forward_cnn(params, x, cfg, plan=plan)
    clean_top1 = np.argmax(np.asarray(clean), -1)

    # the paper's protocol: L epochs, epoch i injects into conv layer i
    for layer in range(len(cfg.convs)):
        _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
        p = inj.plan(jax.random.PRNGKey(layer + 100), o_clean.shape[0],
                     o_clean.shape[1], max_elems=100)
        o_bad = inj.inject_conv(o_clean, p)
        logits, rep = cnn.forward_cnn(params, x, cfg, plan=plan,
                                      inject_layer=layer,
                                      inject_o={layer: o_bad})
        r = rep.by_layer[f"conv{layer}"]          # per-layer attribution
        top1 = np.argmax(np.asarray(logits), -1)
        status = "OK " if np.array_equal(top1, clean_top1) else "DIFF"
        print(f"  layer {layer:2d}: detected={int(r.detected)} "
              f"corrected_by={SCHEME_NAMES[int(r.corrected_by)]:9s} "
              f"residual={int(rep.residual)} top1={status}")


if __name__ == "__main__":
    main()
