"""Operations and bytes of every conv and fc site of a benchmark CNN,
from the shapes in its configuration file alone.

FLOPs count each multiply and each add of the site's own product,
2 x MACs; bias, activation, pooling and every checksum are not counted.
Bytes are what the site's op reads and writes once, in the dtypes it
works in: operands in the configuration's `operand_dtype`, the output
in `accumulate_dtype`.
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp


def scaled(cfg: dict, ch: int) -> int:
    """A layer's width at the configuration's `width_scale` (the system
    rounds the same way; 4 channels at the least)."""
    return max(int(round(ch * cfg["width_scale"])), 4)


def sites(cfg: dict, batch: int) -> List[Dict]:
    """One record per protected site, in forward order: name, shapes,
    flops and bytes of one call at `batch` images."""
    opb = jnp.dtype(cfg["operand_dtype"]).itemsize
    outb = jnp.dtype(cfg["accumulate_dtype"]).itemsize
    out: List[Dict] = []
    ch, hw = cfg["in_ch"], cfg["img"]
    for i, layer in enumerate(cfg["layers"]):
        f, k, s, p = (scaled(cfg, layer["out_ch"]), layer["kernel"],
                      layer["stride"], layer["pad"])
        e = (hw + 2 * p - k) // s + 1
        macs = batch * f * ch * k * k * e * e
        nbytes = (opb * (batch * ch * hw * hw + f * ch * k * k)
                  + outb * batch * f * e * e)
        out.append({"name": f"conv{i}", "kind": "conv", "batch": batch,
                    "in_ch": ch, "out_ch": f, "kernel": k, "in_hw": hw,
                    "out_hw": e, "flops": 2 * macs, "bytes": nbytes})
        ch, hw = f, (e // layer["pool"] if layer["pool"] else e)
    n_cls = cfg["num_classes"]
    out.append({"name": "fc", "kind": "matmul", "batch": batch,
                "in_ch": ch, "out_ch": n_cls,
                "flops": 2 * batch * ch * n_cls,
                "bytes": (opb * (batch * ch + ch * n_cls)
                          + outb * batch * n_cls)})
    return out


def flops_per_image(cfg: dict) -> int:
    return sum(s["flops"] for s in sites(cfg, 1))


def site_min_seconds(site: Dict, peaks: Dict) -> float:
    """The least time the chip could take for one call of the site: the
    larger of its flops over peak FLOP/s and its bytes over peak
    bandwidth."""
    return max(site["flops"] / peaks["flops_per_s"],
               site["bytes"] / peaks["bytes_per_s"])
