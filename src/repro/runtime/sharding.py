"""Logical-axis sharding rules (MaxText-style path-pattern -> PartitionSpec).

Strategy on the (pod, data, model) production mesh:
- batch/sequence activations shard over ('pod','data') [DP]
- attention heads / d_ff / vocab shard over 'model' [TP]
- MoE experts shard over 'model' [EP=TP axis]; expert d_ff additionally
  shards over 'data' (ZeRO-3/FSDP style) - this is what lets the 1T-param
  kimi-k2 weights fit (2 TB bf16 / 256 ways)
- optimizer state mirrors its parameter
- long-context decode KV caches shard sequence over 'data' (context
  parallelism) since batch=1 cannot use the DP axis
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """jax.make_mesh with Auto axis types. The rules below are GSPMD
    rules (PartitionSpec constraints that the compiler propagates); under
    JAX's default Explicit axes every array carries its sharding in its
    type, and ops whose output sharding the rules leave to propagation (a
    reshape of a data-sharded batch, a gather from a vocab-sharded table)
    are rejected. Enter the mesh with `jax.set_mesh`."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# (path regex, spec builder). First match wins. `d` = data axes tuple.
_RULES = [
    # embeddings / heads: vocab over model
    (r"embed/table$",            lambda d: P(None, "model", None)),
    (r"embed/head/w$",           lambda d: P(None, "model")),
    # attention projections
    (r"attn/w[qkv]/w$",          lambda d: P(None, "model")),
    (r"attn/wo/w$",              lambda d: P("model", None)),
    # dense ffn
    (r"ffn/(gate|up)/w$",        lambda d: P(None, "model")),
    (r"ffn/down/w$",             lambda d: P("model", None)),
    # moe: experts over model (EP); expert d_ff over data (FSDP)
    (r"moe/router/w$",           lambda d: P(None, None)),
    (r"moe/(gate|up)$",          lambda d: P("model", None, d)),
    (r"moe/down$",               lambda d: P("model", d, None)),
    (r"moe/shared/(gate|up)/w$", lambda d: P(None, "model")),
    (r"moe/shared/down/w$",      lambda d: P("model", None)),
    # mamba2
    (r"ssm/in_proj/w$",          lambda d: P(None, "model")),
    (r"ssm/out_proj/w$",         lambda d: P("model", None)),
    (r"ssm/conv_w$",             lambda d: P(None, "model")),
    # rg-lru
    (r"rec/(in_x|in_gate)/w$",   lambda d: P(None, "model")),
    (r"rec/(gate_a|gate_i)/w$",  lambda d: P(None, "model")),
    (r"rec/out/w$",              lambda d: P("model", None)),
    (r"rec/conv_w$",             lambda d: P(None, "model")),
    (r"rec/lam$",                lambda d: P("model")),
    # adafactor factored second-moment for expert weights
    (r"moe/(gate|up|down)/(r|c)$", lambda d: P("model", None)),
]


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def head_ok(ps: str, cfg, tp: int) -> bool:
    """Attention projections shard over 'model' only when the head count
    divides the axis (otherwise the (B,S,H,hd) reshape would regather
    every layer); cfg=None disables the check."""
    if cfg is None:
        return True
    if re.search(r"attn/(wq|wo)/w$", ps):
        return cfg.num_heads % tp == 0
    if re.search(r"attn/w[kv]/w$", ps):
        return cfg.num_kv_heads % tp == 0
    return True


def spec_for_param(path: str, ndim: int, mesh: Mesh) -> P:
    d = data_axes(mesh)
    d = d if len(d) > 1 else (d[0] if d else None)
    for pat, fn in _RULES:
        if re.search(pat, path):
            spec = fn(d)
            if len(spec) > ndim:           # stacked-stage leading axis
                spec = P(*spec[:ndim])
            return spec
    return P()                              # replicate (norms, scalars, ...)


def param_shardings(params, mesh: Mesh, cfg=None, dp_only: bool = False,
                    fsdp: bool = False):
    """Pytree of NamedSharding for a param tree. Stacked stage params (one
    extra leading axis from vmap-init) keep the rule of their block with
    the stage axis replicated.

    Head-aware: attention projections shard over 'model' only when the
    head count divides the axis (otherwise the (B,S,H,hd) reshape would
    regather every layer); pass `cfg` to enable the check.

    Perf-policy knobs (SSPerf): dp_only replicates all params (small
    models where TP redundancy dominates - batch then shards over both
    axes); fsdp additionally shards each weight's first 'model'-free axis
    over 'data' (ZeRO-3: all-gather at use, frees HBM)."""
    tp = mesh.shape.get("model", 1)

    def _head_ok(ps: str) -> bool:
        return head_ok(ps, cfg, tp)

    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        ps = _path_str(path)
        # stacked stages (anywhere in the tree - params or optimizer
        # mirrors): rules describe the unstacked block; prepend a
        # replicated stage axis
        stacked = "stages/" in ps or ps.startswith("stages")
        base_ndim = leaf.ndim - (1 if stacked else 0)
        if dp_only:
            inner = P(*([None] * base_ndim))
        elif _head_ok(ps):
            inner = spec_for_param(ps, base_ndim, mesh)
        else:
            inner = P(*([None] * base_ndim))
        if fsdp and not dp_only and base_ndim >= 2:
            # shard the first model-free axis over data (ZeRO-3)
            names = list(inner) + [None] * (base_ndim - len(inner))
            if "data" not in str(names):
                for i, nm in enumerate(names):
                    if nm is None:
                        names[i] = "data"
                        break
            inner = P(*names)
        spec = P(None, *inner) if stacked else inner
        spec = _legalize(spec, leaf.shape, mesh)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(tdef, out)


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        size = 1
        for n in name:
            size *= mesh.shape[n]
        return size
    return mesh.shape[name]


def _legalize(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on axes that do not divide evenly (e.g. 8 kv heads on
    a 16-way model axis) - replicate instead of failing."""
    out = []
    for i, name in enumerate(spec):
        if name is None or i >= len(shape):
            out.append(None)
            continue
        out.append(name if shape[i] % _axis_size(mesh, name) == 0 else None)
    return P(*out)


def maybe_constrain(x, *spec):
    """with_sharding_constraint that no-ops when no mesh is in scope
    (single-device runs); inside the dry-run / drivers the mesh context
    is active and the constraint pins GSPMD's propagation. Any error of
    the constraint itself propagates."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def batch_spec(mesh: Mesh) -> P:
    d = data_axes(mesh)
    return P(d if len(d) > 1 else (d[0] if d else None))


def activation_shardings(mesh: Mesh, tokens_ndim: int = 2) -> NamedSharding:
    spec = batch_spec(mesh)
    return NamedSharding(mesh, P(*spec, *([None] * (tokens_ndim - 1))))


def cache_shardings(caches, mesh: Mesh, batch: int):
    """Serving-state shardings. Batch shards over the DP axes when it
    divides; otherwise (long_500k, batch=1) attention KV shards its
    *sequence* axis over 'data' - context-parallel decode. KV heads shard
    over 'model' when divisible."""
    d = data_axes(mesh)
    dsize = 1
    for a in d:
        dsize *= mesh.shape[a]
    d_spec = d if len(d) > 1 else (d[0] if d else None)
    batch_ok = batch % dsize == 0

    flat, tdef = jax.tree_util.tree_flatten_with_path(caches)
    out = []
    for path, leaf in flat:
        ps = _path_str(path)
        stacked = "stages/" in ps or ps.startswith("stages")
        base = leaf.shape[1:] if stacked else leaf.shape
        name = ps.rsplit("/", 1)[-1]
        bspec = d_spec if batch_ok else None
        if name in ("k", "v"):            # (B, L, Hkv, hd)
            spec = (bspec, None if batch_ok else "data", "model", None)
        elif name == "h" and len(base) == 4:   # ssm state (B, H, P, N)
            spec = (bspec, "model", None, None)
        elif name == "h":                  # rg-lru state (B, W)
            spec = (bspec, "model")
        elif name == "conv":               # conv tail (B, K-1, C)
            spec = (bspec, None, "model")
        else:
            spec = (bspec,) + (None,) * (len(base) - 1)
        spec = _legalize(P(*spec), base, mesh)
        if stacked:
            spec = P(None, *spec)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(tdef, out)


def checksum_shardings(plan, mesh: Mesh, cfg=None):
    """{entry name -> (cw1 sharding, cw2 sharding)} placing each matmul
    entry's weight checksums by the SAME rule as the weight they encode:
    a (K, M) weight with spec (kspec, mspec) has (M/chunk, K) checksums,
    so the checksum spec is the transposed weight spec - column-sharded
    weights get row-sharded checksums and the protected contraction runs
    against colocated shards. Conv checksums, w_view entries (weight
    views don't follow the leaf rule) and anything without the matmul
    (blocks, K) layout replicate. Stacked entries keep a replicated
    leading stage axis, mirroring param_shardings."""
    repl = NamedSharding(mesh, P())
    tp = mesh.shape.get("model", 1)
    out = {}
    for name, e in plan.entries.items():
        if e.wck is None:
            continue
        if (e.op.kind != "matmul" or e.w_view is not None
                or not hasattr(e.wck, "col_chunk")):
            out[name] = (repl, repl)
            continue
        ps = name + "/w"
        if not head_ok(ps, cfg, tp):
            out[name] = (repl, repl)
            continue
        if e.stack:
            # scanned-stage checksums ride the scan's xs into the deferred
            # cond; on this XLA (CPU SPMD) a K-sharded xs there hits an
            # "involuntary full rematerialization" in the partitioner that
            # double-counts the checksum-side contraction (c == 2*s, a
            # guaranteed false positive). Replicating ON the mesh is clean
            # and the arrays are O(K) - placement, not partitioning, is
            # what keeps them colocated with the scan.
            out[name] = (repl, repl)
            continue
        wspec = spec_for_param(ps, 2, mesh)
        names = list(wspec) + [None] * (2 - len(wspec))
        cspec = _legalize(P(names[1], names[0]),
                          tuple(e.wck.cw1.shape), mesh)
        sh = NamedSharding(mesh, cspec)
        out[name] = (sh, sh)
    return out
