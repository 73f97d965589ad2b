"""The paper's four CNNs - AlexNet, VGG-19, ResNet-18, YOLOv2 (Darknet-19
backbone) - built on the protected convolution, with per-layer scheme
policy (paper SS4.3) and fault-report aggregation.

These are the FT-Caffe reproduction targets: the benchmarks measure the
overhead figures of Fig. 6 / Fig. 10 / Table 6 on them. Configs are
scalable so the CPU-only container runs reduced widths while keeping every
layer shape ratio.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import (DEFAULT_CONFIG, ModelReport, ProtectConfig,
                        ProtectedModel, ProtectionPlan, build_plan,
                        conv_entry, protect_site, resolve_entry)
from repro.core.plan import ambient_plan, current_path
from repro.core.protected import op_matmul

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    out_ch: int
    kernel: int
    stride: int = 1
    pad: int = 0
    pool: int = 0          # maxpool after conv (kernel=stride=pool)
    residual_from: int = -1  # resnet shortcut source (layer idx)


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    convs: Tuple[ConvSpec, ...]
    in_ch: int = 3
    img: int = 224
    num_classes: int = 1000
    width_scale: float = 1.0
    abft: bool = True

    def scaled(self, c: int) -> int:
        return max(int(round(c * self.width_scale)), 4)


def alexnet(scale: float = 1.0) -> CNNConfig:
    return CNNConfig("alexnet", (
        ConvSpec(96, 11, 4, 2, pool=2), ConvSpec(256, 5, 1, 2, pool=2),
        ConvSpec(384, 3, 1, 1), ConvSpec(384, 3, 1, 1),
        ConvSpec(256, 3, 1, 1, pool=2)), width_scale=scale)


def vgg19(scale: float = 1.0) -> CNNConfig:
    spec: List[ConvSpec] = []
    for ch, reps in ((64, 2), (128, 2), (256, 4), (512, 4), (512, 4)):
        for i in range(reps):
            spec.append(ConvSpec(ch, 3, 1, 1, pool=2 if i == reps - 1 else 0))
    return CNNConfig("vgg19", tuple(spec), width_scale=scale)


def resnet18(scale: float = 1.0) -> CNNConfig:
    spec: List[ConvSpec] = [ConvSpec(64, 7, 2, 3, pool=2)]
    for stage_i, ch in enumerate((64, 128, 256, 512)):
        for block in range(2):
            stride = 2 if (stage_i > 0 and block == 0) else 1
            spec.append(ConvSpec(ch, 3, stride, 1))
            # identity shortcut only where it is shape-valid: downsampling
            # blocks (stride 2 halves spatial, doubles channels) would need
            # a projection shortcut, which this plain-conv stack does not
            # model - forward_cnn rejects mismatched shortcuts at trace
            # time, so don't declare them here
            spec.append(ConvSpec(ch, 3, 1, 1,
                                 residual_from=len(spec) - 2
                                 if stride == 1 else -1))
    return CNNConfig("resnet18", tuple(spec), width_scale=scale)


def yolov2(scale: float = 1.0) -> CNNConfig:
    """Darknet-19 backbone (YOLOv2's conv layers)."""
    spec = [ConvSpec(32, 3, 1, 1, pool=2), ConvSpec(64, 3, 1, 1, pool=2),
            ConvSpec(128, 3, 1, 1), ConvSpec(64, 1), ConvSpec(128, 3, 1, 1, pool=2),
            ConvSpec(256, 3, 1, 1), ConvSpec(128, 1), ConvSpec(256, 3, 1, 1, pool=2),
            ConvSpec(512, 3, 1, 1), ConvSpec(256, 1), ConvSpec(512, 3, 1, 1),
            ConvSpec(256, 1), ConvSpec(512, 3, 1, 1, pool=2),
            ConvSpec(1024, 3, 1, 1), ConvSpec(512, 1), ConvSpec(1024, 3, 1, 1),
            ConvSpec(512, 1), ConvSpec(1024, 3, 1, 1)]
    return CNNConfig("yolov2", tuple(spec), img=416, width_scale=scale)


CNN_REGISTRY = {"alexnet": alexnet, "vgg19": vgg19, "resnet18": resnet18,
                "yolov2": yolov2}


# --------------------------------------------------------------------------

def init_cnn(key, cfg: CNNConfig, dtype=jnp.float32) -> Dict:
    params: Dict[str, Any] = {}
    ch = cfg.in_ch
    keys = jax.random.split(key, len(cfg.convs) + 1)
    for i, spec in enumerate(cfg.convs):
        out = cfg.scaled(spec.out_ch)
        fan_in = ch * spec.kernel ** 2
        params[f"conv{i}"] = {
            "w": (jax.random.normal(keys[i], (out, ch, spec.kernel,
                                              spec.kernel), F32)
                  * (2.0 / fan_in) ** 0.5).astype(dtype),
            "b": jnp.zeros((out,), dtype),
        }
        ch = out
    params["fc"] = {
        "w": (jax.random.normal(keys[-1], (ch, cfg.num_classes), F32)
              * ch ** -0.5).astype(dtype),
        "b": jnp.zeros((cfg.num_classes,), dtype)}
    return params


def layer_policies(cfg: CNNConfig, batch: int) -> List[ProtectConfig]:
    """Deprecated shim: per-layer RC/ClC policy now lives in
    `repro.core.build_plan` (which also precomputes weight checksums).
    This returns only the conv configs of a policy-only plan."""
    plan = build_plan(None, cfg, batch=batch)
    return [plan[f"conv{i}"].cfg for i in range(len(cfg.convs))]


def _maxpool(x: jnp.ndarray, k: int) -> jnp.ndarray:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, k, k), (1, 1, k, k), "VALID")


def _forward_pass(params: Dict, x: jnp.ndarray, cfg: CNNConfig,
                  policies: Optional[Sequence[ProtectConfig]],
                  inject_layer: int, inject_o,
                  ) -> Tuple[jnp.ndarray, List[str], List]:
    """The shared layer walk behind both correction regimes: returns
    (logits, protected-layer names, per-layer carries) where the carries
    are FaultReports (ambient mode None/"correct") or DetectEvidence
    ("detect_only"). Entries resolve from the ambient plan context (the
    ProtectedModel session); without a plan, each conv builds a per-call
    entry from `policies[i]` / the arch default. Execution mode and the
    deferred rerun's carried CoC-D flags are ambient too - this walk is
    model code, not workflow code."""
    names: List[str] = []
    carries: List[Any] = []
    feats = []
    for i, spec in enumerate(cfg.convs):
        name = f"conv{i}"
        entry = resolve_entry(name)
        if entry is None:
            if ambient_plan() is not None:
                # an active plan that skips a conv layer is a plan/arch
                # mismatch: silently protecting it with the default
                # config (and a per-call weight encode) would diverge
                # from the compiled policy - fail like plan[name] used to
                raise KeyError(
                    f"forward_cnn: the active ProtectionPlan has no "
                    f"entry for {name!r}; rebuild the plan with "
                    "build_plan() or run without one")
            entry = conv_entry(
                name, cfg=(policies[i] if policies is not None else
                           (DEFAULT_CONFIG if cfg.abft else
                            DEFAULT_CONFIG.replace(enabled=False))),
                stride=spec.stride, pad=spec.pad)
        o = _injected_output(i, name, x, params[name], spec, inject_layer,
                             inject_o)
        y, r = protect_site(name,
                            (x, params[name]["w"], params[name]["b"]),
                            entry=entry, o=o)
        names.append(name)
        carries.append(r)
        if spec.residual_from >= 0:
            short = feats[spec.residual_from]
            if short.shape != y.shape:
                raise ValueError(
                    f"forward_cnn: conv layer {i} declares a residual "
                    f"shortcut from layer {spec.residual_from}, but the "
                    f"shortcut shape {tuple(short.shape)} does not match "
                    f"the conv output shape {tuple(y.shape)}; identity "
                    "shortcuts require equal shapes (use a projection or "
                    "drop residual_from)")
            y = y + short
        y = jax.nn.relu(y)
        if spec.pool:
            y = _maxpool(y, spec.pool)
        feats.append(y)
        x = y
    x = jnp.mean(x, axis=(2, 3))                     # global average pool
    fc_entry = resolve_entry("fc")
    if fc_entry is not None:
        logits, r = protect_site("fc",
                                 (x, params["fc"]["w"], params["fc"]["b"]),
                                 entry=fc_entry)
        names.append("fc")
        carries.append(r)
    else:
        # the protected GEMM's own arithmetic, so a plan-less forward is
        # bitwise the one a plan protects
        w, b = params["fc"]["w"], params["fc"]["b"]
        logits = op_matmul(x, w).astype(x.dtype) + b.astype(x.dtype)
    return logits, names, carries


def forward_cnn(params: Dict, x: jnp.ndarray, cfg: CNNConfig,
                policies: Optional[Sequence[ProtectConfig]] = None,
                inject_layer: int = -1,
                inject_o: Optional[Dict[int, jnp.ndarray]] = None, *,
                plan: Optional[ProtectionPlan] = None,
                correction: str = "per_layer",
                ) -> Tuple[jnp.ndarray, ModelReport]:
    """x: (N, C, H, W) -> (logits, per-layer ModelReport).

    `plan` is the offline-compiled ProtectionPlan (build_plan): per-layer
    policy + precomputed weight checksums, and protection of the final fc
    GEMM. Without a plan, each conv re-derives its weight checksums per
    call under `policies[i]` (legacy shim) or the all-default config.
    inject_layer/inject_o: test hook - replaces layer i's conv output with
    a corrupted tensor before protection (the paper's per-layer injection).
    `inject_o` is a {layer: corrupted output} dict and `inject_layer` (an
    int or a traced scalar) picks the one entry injected; traced, one
    compiled program serves every listed layer (and a clean run, for a
    value that lists none).

    `correction` picks the workflow granularity:
    * "per_layer" (default) - every protected op carries its own in-graph
      lax.cond correction ladder;
    * "deferred" - the whole forward runs detect-only (one compact
      DetectEvidence carry per layer), then ONE model-level lax.cond
      reruns the protected forward with full correction only when any
      layer flagged (the paper's fuse-then-defer multischeme discipline,
      in-graph). Error-free, the model carries a single cond instead of
      one per layer; verdict attribution is preserved via the detect-pass
      flags, and corrected logits are bitwise-identical to the per-layer
      path (the rerun is the per-layer computation).

    forward_cnn is a thin shim over the model-agnostic
    `core.ProtectedModel` session - the layer walk above is the only
    CNN-specific part; the deferred workflow, carried flags and report
    assembly are the same code the transformer runs.
    """
    def apply_fn(p, xx):
        logits, names, carries = _forward_pass(p, xx, cfg, policies,
                                               inject_layer, inject_o)
        return logits, ModelReport(dict(zip(names, carries)))

    return ProtectedModel(apply_fn, plan)(params, x, correction=correction)


def _conv_output(x: jnp.ndarray, p: Dict, spec: ConvSpec) -> jnp.ndarray:
    """A conv layer's complete output (bias included), computed as the
    protected op computes it."""
    from repro.core.checksums import conv2d
    o = conv2d(x, p["w"], stride=spec.stride,
               padding=[(spec.pad, spec.pad)] * 2)
    return (o.astype(F32) + p["b"][None, :, None, None]).astype(o.dtype)


def _injected_output(i: int, name: str, x, p: Dict, spec: ConvSpec,
                     inject_layer, inject_o):
    """Layer i's output under the injection hook (None: not injected),
    under the site's scope: its conv as the site's `op`, the choice of
    the planted output as `inject`. The choice's compare is traced
    before the conv: traced after it, the corrective rerun's operands
    come in another order and the step's HLO changes."""
    if not inject_o or i not in inject_o:
        return None
    with jax.named_scope(current_path(name)):
        with jax.named_scope("inject"):
            hit = inject_layer == i
        with jax.named_scope("op"):
            o = _conv_output(x, p, spec)
        with jax.named_scope("inject"):
            return jnp.where(hit, inject_o[i], o)


def conv_output_at(params: Dict, x: jnp.ndarray, cfg: CNNConfig,
                   layer: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(input_to_layer, clean_conv_output_of_layer) for injection tests:
    the forward's own layer walk, residual shortcuts included."""
    feats = []
    for i, spec in enumerate(cfg.convs):
        o = _conv_output(x, params[f"conv{i}"], spec)
        if i == layer:
            return x, o
        y = o if spec.residual_from < 0 else o + feats[spec.residual_from]
        y = jax.nn.relu(y)
        if spec.pool:
            y = _maxpool(y, spec.pool)
        feats.append(y)
        x = y
    raise ValueError(layer)
