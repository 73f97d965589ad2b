"""Fig. 15: parallel scalability of the protection overhead. Batch-parallel
protected inference over 1/2/4-device ("data",) meshes of this process's
devices, all in one process (a chip belongs to one process at a time).
The paper's claim: overhead does not grow with node count. Counts beyond
the devices present are reported as skipped."""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import cnn
from repro.runtime.sharding import make_mesh

from .common import row, time_fn

PER_DEVICE_BATCH = 8


def run(device_counts=(1, 2, 4)):
    print("# Fig15: protection overhead vs device count")
    devs = jax.devices()
    cfg = dataclasses.replace(cnn.alexnet(0.12), img=64)
    off = dataclasses.replace(cfg, abft=False)
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    f_plain = jax.jit(lambda p, x: cnn.forward_cnn(p, x, off)[0])
    f_prot = jax.jit(lambda p, x: cnn.forward_cnn(p, x, cfg)[0])
    out = []
    for n in device_counts:
        if n > len(devs):
            out.append(row(f"fig15/devices{n}", -1,
                           f"skipped:{len(devs)}_devices"))
            continue
        mesh = make_mesh((n,), ("data",), devices=devs[:n])
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (PER_DEVICE_BATCH * n, 3, 64, 64))
        x = jax.device_put(x, NamedSharding(mesh, P("data")))
        p = jax.device_put(params, NamedSharding(mesh, P()))
        with jax.set_mesh(mesh):
            t0 = time_fn(f_plain, p, x)
            t1 = time_fn(f_prot, p, x)
        out.append(row(f"fig15/devices{n}", t1 * 1e6,
                       f"overhead_pct={(t1 - t0) / t0 * 100:.2f};"
                       f"platform={devs[0].platform}"))
    return out


if __name__ == "__main__":
    run()
