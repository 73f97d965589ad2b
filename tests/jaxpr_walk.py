"""The one jaxpr walker behind the structure guards (one launch per GEMM,
ladder only inside the cond), on JAX's supported `jax.extend.core`."""
import jax
from jax.extend.core import ClosedJaxpr, Jaxpr


def _is_jaxpr(x) -> bool:
    return isinstance(x, (Jaxpr, ClosedJaxpr))


def eqns(jaxpr, skip=(), opaque=()):
    """Equations of `jaxpr` and of every inner jaxpr, depth first.
    Equations whose primitive is in `skip` are dropped with their bodies
    (e.g. "cond": the correction ladder); those in `opaque` are kept but
    not entered (e.g. "pallas_call": a kernel's own body)."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in skip:
            continue
        out.append(eqn)
        if name in opaque:
            continue
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(v, is_leaf=_is_jaxpr):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    out.extend(eqns(sub, skip, opaque))
    return out
