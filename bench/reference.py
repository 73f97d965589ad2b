"""The plain reference of the benchmark's CNNs: their forward pass in
straightforward jax.numpy and float32, from the layer list of the
configuration file. It imports nothing of the system under test.

Every product runs at HIGHEST precision on operands rounded to
`operand`, which is the precision the configuration states for its op
(`operand_dtype`: the op multiplies bfloat16 operands with float32
accumulation). With `operand` set to the configuration's
`control_operand_dtype` the same code is the control: int8 operands,
each tensor scaled symmetrically by its largest magnitude.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def operand(x: jnp.ndarray, dtype: str) -> jnp.ndarray:
    """`x` in float32, holding only values of `dtype` (int8: scaled by
    its largest magnitude over 127)."""
    x = x.astype(F32)
    if dtype == "float32":
        return x
    if dtype == "int8":
        s = jnp.max(jnp.abs(x)) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    fi = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _maxpool(x, k):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, k, k),
                                 (1, 1, k, k), "VALID")


def forward(params: dict, x: jnp.ndarray, layers, dtype: str):
    """x (N, C, H, W) -> logits (N, classes) in float32.

    Each layer: conv + bias, the identity shortcut where one is declared,
    ReLU, then a max-pool of kernel = stride = `pool` where it is not 0.
    Then a global mean-pool and the fc layer."""
    feats = []
    for i, layer in enumerate(layers):
        p = params[f"conv{i}"]
        pad = layer["pad"]
        y = jax.lax.conv_general_dilated(
            operand(x, dtype), operand(p["w"], dtype),
            (layer["stride"],) * 2, [(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
        y = y + p["b"].astype(F32)[None, :, None, None]
        if layer["residual_from"] >= 0:
            y = y + feats[layer["residual_from"]]
        y = jax.nn.relu(y)
        if layer["pool"]:
            y = _maxpool(y, layer["pool"])
        feats.append(y)
        x = y
    x = jnp.mean(x, axis=(2, 3))
    w, b = params["fc"]["w"], params["fc"]["b"]
    return jnp.dot(operand(x, dtype), operand(w, dtype),
                   precision=HIGHEST) + b.astype(F32)


def make(layers, dtype: str):
    """The jitted reference for one configuration's layer list."""
    return jax.jit(partial(forward, layers=list(layers), dtype=dtype))
