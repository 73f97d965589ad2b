"""One benchmark cell: protected CNN inference through the system's
normal path, closed loop, from a configuration file and a traffic file.

set-up   weights and inputs from the seed (one jitted call each), the
         analytic protection plan (`build_plan`), the timed step lowered
         and compiled (or loaded from the compile cache), the fault
         requests of the traffic's schedule, and a warm-up of every
         request shape;
window   requests in the schedule's order, up to `in_flight` in flight,
         for `seconds`; a request counts when its logits are on the host
         inside the window;
check    once the window has closed, memory read and the program's state
         freed: every request's logits against the plain reference
         (`reference.py`) and every request's fault report against the
         schedule.

The timed step is `forward_cnn(params, x, cfg, plan=plan,
correction=<the configuration's>)` under one `jax.jit` whose arguments
are the parameters, the input batch, the plan's weight checksums and,
where the schedule injects faults, the injection hook's arguments. The
plan's other fields are static. So every seed of a cell lowers to the
same program, and a warm run finds it in the compile cache.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

F32 = jnp.float32
# Sizes every cell shares: the distinct input batches a run cycles
# through, the seconds of the loop run in set-up before the window, and
# the images of one call of the reference in the check.
POOL = 4
WARMUP_SECONDS = 1.0
REFERENCE_BLOCK = 16


class Counter:
    """Counts the programs JAX compiles or loads from the cache."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def compiled(self) -> int:
        return self.requests - self.hits


def key_from_seed(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it and the
    rest is folded in, so seeds above 2**32 stay distinct."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def program_config(cfg: dict):
    """The system's CNNConfig for the configuration file; its layer list
    must be the file's, which the reference and flops.py read."""
    from repro.models import cnn
    pcfg = dataclasses.replace(
        cnn.CNN_REGISTRY[cfg["registry"]](cfg["width_scale"]),
        img=cfg["img"], in_ch=cfg["in_ch"], num_classes=cfg["num_classes"])
    got = [dataclasses.asdict(s) for s in pcfg.convs]
    if got != [dict(layer) for layer in cfg["layers"]]:
        raise ValueError(f"{cfg['name']}: the system's {cfg['registry']} "
                         "layer list differs from the configuration file")
    return pcfg


def init_params(key, cfg: dict) -> Dict:
    """He-normal conv weights and small normal biases, in the layout the
    system takes ({"conv<i>": {"w", "b"}, "fc": {"w", "b"}})."""
    from .flops import scaled
    dt = jnp.dtype(cfg["param_dtype"])
    params: Dict = {}
    ch = cfg["in_ch"]
    keys = jax.random.split(key, 2 * len(cfg["layers"]) + 2)
    for i, layer in enumerate(cfg["layers"]):
        out, k = scaled(cfg, layer["out_ch"]), layer["kernel"]
        std = (2.0 / (ch * k * k)) ** 0.5
        params[f"conv{i}"] = {
            "w": (jax.random.normal(keys[2 * i], (out, ch, k, k), F32)
                  * std).astype(dt),
            "b": (jax.random.normal(keys[2 * i + 1], (out,), F32)
                  * 0.01).astype(dt)}
        ch = out
    n_cls = cfg["num_classes"]
    params["fc"] = {
        "w": (jax.random.normal(keys[-2], (ch, n_cls), F32)
              * ch ** -0.5).astype(dt),
        "b": (jax.random.normal(keys[-1], (n_cls,), F32) * 0.01).astype(dt)}
    return params


def init_inputs(key, cfg: dict, batch: int, pool: int):
    """`pool` distinct input batches, standard normal images."""
    keys = jax.random.split(key, pool)
    shape = (batch, cfg["in_ch"], cfg["img"], cfg["img"])
    return tuple(jax.random.normal(k, shape, F32) for k in keys)


def fault_layers(cfg: dict, names) -> List[int]:
    last = len(cfg["layers"]) - 1
    where = {"first": 0, "middle": last // 2, "last": last}
    return [where[n] for n in names]


# --------------------------------------------------------------------------
# the timed step
# --------------------------------------------------------------------------

def checksum_arrays(plan) -> Dict:
    """The array leaves of each plan entry's weight checksums."""
    return {n: tuple(e.wck)[:2] for n, e in plan.entries.items()
            if e.wck is not None}


def _with_checksums(plan, wcks):
    entries = {}
    for n, e in plan.entries.items():
        if n in wcks:
            arrs = wcks[n]
            wck = (type(e.wck)(*arrs, e.wck.col_chunk)
                   if hasattr(e.wck, "col_chunk") else tuple(arrs))
            e = dataclasses.replace(e, wck=wck)
        entries[n] = e
    return dataclasses.replace(plan, entries=entries)


def make_step(pcfg, plan, correction: str, sites: List[str]):
    """The jitted step: (params, x, wcks[, inject_layer, inject_o]) ->
    (logits, verdicts), verdicts an int32 (sites, 3) array of each
    site's (detected, corrected_by, residual)."""
    from repro.models import cnn

    def bench_step(params, x, wcks, inject_layer=None, inject_o=None):
        hook = ({} if inject_o is None else
                {"inject_layer": inject_layer, "inject_o": inject_o})
        logits, rep = cnn.forward_cnn(params, x, pcfg,
                                      plan=_with_checksums(plan, wcks),
                                      correction=correction, **hook)
        verdicts = jnp.stack([
            jnp.stack([rep.by_layer[n].detected, rep.by_layer[n].corrected_by,
                       rep.by_layer[n].residual]).astype(jnp.int32)
            for n in sites])
        return logits, verdicts

    return jax.jit(bench_step)


def make_faults(params, xs, cfg: dict, pcfg, traffic: dict, key):
    """The schedule's faults: for each fault layer, `per_layer` faults,
    each one block row or column of that layer's clean conv output
    (`core.injection.plan`, up to `max_elems` elements) on an input
    batch drawn from the seed. Returns [(layer, item, corrupted o)]."""
    from repro.core import injection
    from repro.models import cnn
    spec = traffic["faults"]
    layers = fault_layers(cfg, spec["layers"])
    todo = []
    for li, layer in enumerate(layers):
        for j in range(spec["per_layer"]):
            k = jax.random.fold_in(key, li * 1000 + j)
            item = int(jax.random.randint(jax.random.fold_in(k, 1), (), 0,
                                          len(xs)))
            todo.append((layer, item, jax.random.fold_in(k, 2)))

    def corrupt(params, x, k, layer):
        _, o = cnn.conv_output_at(params, x, pcfg, layer)
        p = injection.plan(k, o.shape[0], o.shape[1],
                           max_elems=spec["max_elems"])
        return injection.inject_conv(o, p)

    fn = jax.jit(corrupt, static_argnums=3)
    return [(layer, item, fn(params, xs[item], k, layer))
            for layer, item, k in todo]


def build_requests(params, xs, wcks, faults):
    """The step's arguments of every request, and what each request is:
    (input batch, fault layer or -1). Clean requests come first, one per
    input batch, then one per fault."""
    if faults is None:
        return ([(params, x, wcks) for x in xs],
                [(i, -1) for i in range(len(xs))])
    blank = {layer: jnp.zeros_like(o) for layer, _, o in faults}
    none = jnp.int32(-1)
    args = [(params, x, wcks, none, blank) for x in xs]
    meta = [(i, -1) for i in range(len(xs))]
    for layer, item, o in faults:
        args.append((params, xs[item], wcks, jnp.int32(layer),
                     {**blank, layer: o}))
        meta.append((item, layer))
    return args, meta


def schedule(n_clean: int, n_faults: int, traffic: dict, key):
    """Request index of step s: clean batches in turn; with faults, every
    `every`-th step injects the next fault of an order drawn from the
    seed."""
    order = (np.asarray(jax.random.permutation(key, n_faults))
             if n_faults else None)
    every = traffic["faults"]["every"] if n_faults else 0

    def pick(s: int) -> int:
        if every and s % every == every - 1:
            return n_clean + int(order[(s // every) % n_faults])
        return s % n_clean
    return pick


# --------------------------------------------------------------------------
# window
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Done:
    req: int
    t_dispatch: float
    t_done: float
    logits: np.ndarray
    verdicts: np.ndarray


def window(step, requests, pick, seconds: float, in_flight: int,
           first_step: int = 0):
    """Closed loop for `seconds`: keeps up to `in_flight` steps in flight
    and waits for the oldest. Returns (finished steps, window end on the
    host clock, steps dispatched). Steps still in flight at the end are
    drained and returned too; their t_done is past the end."""
    ann = jax.profiler.TraceAnnotation
    pending = []
    done: List[Done] = []
    s = first_step
    end = time.perf_counter() + seconds
    while True:
        now = time.perf_counter()
        while len(pending) < in_flight and now < end:
            r = pick(s)
            with ann("dispatch"):
                out = step(*requests[r])
            pending.append((r, now, out))
            s += 1
            now = time.perf_counter()
        if not pending:
            break
        r, t_disp, out = pending.pop(0)
        with ann("fetch"):
            logits, verdicts = jax.device_get(out)
        t_done = time.perf_counter()
        with ann("check"):
            done.append(Done(r, t_disp, t_done, logits, verdicts))
    return done, end, s - first_step


# --------------------------------------------------------------------------
# check
# --------------------------------------------------------------------------

def logit_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest gap of a request's logits from the reference's, per image
    relative to the reference's largest logit magnitude."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.max(np.abs(want), axis=-1)
    gap = np.max(np.abs(got - want), axis=-1) / np.maximum(scale, 1e-30)
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(gap))


def reference_logits(params, xs, cfg: dict, dtype: str, block: int):
    """The reference's logits for every input batch, computed over all
    their images together, `block` images per call."""
    ref = reference.make(cfg["layers"], dtype)
    x = jnp.concatenate(xs)
    out = np.concatenate([np.asarray(ref(params, x[i:i + block]))
                          for i in range(0, x.shape[0], block)])
    return np.split(out, len(xs))


def verdict_ok(v: np.ndarray, fault_layer: int) -> bool:
    """A clean request flags nothing; a faulted one flags its layer alone,
    names a correcting scheme there and leaves no residual."""
    detected, by, resid = v[:, 0], v[:, 1], v[:, 2]
    if fault_layer < 0:
        return not detected.any() and not resid.any()
    others = np.delete(detected, fault_layer)
    return (detected[fault_layer] == 1 and by[fault_layer] != 0
            and not resid.any() and not others.any())


def check(done: List[Done], meta, refs) -> dict:
    """Every request's logits against the reference of its input batch,
    and every request's report against the schedule."""
    gap = 0.0
    false_alarms = missed = 0
    bad_images = 0
    for d in done:
        item, fault_layer = meta[d.req]
        g = logit_gap(d.logits, refs[item])
        ok = verdict_ok(d.verdicts, fault_layer)
        if not ok:
            if fault_layer < 0:
                false_alarms += 1
            else:
                missed += 1
        if not ok or not g <= LIMITS["logit_gap"]:
            bad_images += d.logits.shape[0]
        gap = max(gap, g)
    numbers = {"logit_gap": gap, "false_alarms": false_alarms,
               "missed_faults": missed}
    return {"numbers": numbers, "bad_images": bad_images}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Prepared:
    """A cell's set-up for one seed, up to the compiled step."""
    params: Dict
    xs: tuple
    plan: object
    requests: list       # the step's arguments, per request
    meta: list           # (input batch, fault layer or -1), per request
    pick: object         # step number -> request
    plan_s: float


def prepare(cfg: dict, traffic: dict, seed: int, pcfg) -> Prepared:
    """Weights, inputs, plan and requests of one seed."""
    from repro.core import build_plan
    if cfg["plan"] != "analytic":
        raise ValueError(f"plan kind {cfg['plan']!r}: only 'analytic'")
    batch = traffic["batch"]
    kp, kx, kf, ko = jax.random.split(key_from_seed(seed), 4)
    params = jax.jit(partial(init_params, cfg=cfg))(kp)
    xs = jax.jit(partial(init_inputs, cfg=cfg, batch=batch,
                         pool=POOL))(kx)
    jax.block_until_ready((params, xs))

    t = time.perf_counter()
    plan = build_plan(params, pcfg, batch=batch)
    wcks = checksum_arrays(plan)
    jax.block_until_ready(wcks)
    plan_s = time.perf_counter() - t

    faults = (make_faults(params, xs, cfg, pcfg, traffic, kf)
              if traffic["faults"] else None)
    requests, meta = build_requests(params, xs, wcks, faults)
    pick = schedule(len(xs), len(faults or ()), traffic, ko)
    return Prepared(params, xs, plan, requests, meta, pick, plan_s)


def compile_step(prep: Prepared, pcfg, cfg: dict):
    """The step lowered and compiled (or loaded from the compile cache)
    for the shapes of the first request, which all requests share."""
    step = make_step(pcfg, prep.plan, cfg["correction"],
                     list(prep.plan.entries))
    return step.lower(*prep.requests[0]).compile()


def warm_up(compiled, prep: Prepared, traffic: dict) -> int:
    """Every request once, then the loop itself for `WARMUP_SECONDS`;
    returns the number of the next step."""
    jax.block_until_ready([compiled(*r) for r in prep.requests])
    return window(compiled, prep.requests, prep.pick,
                  WARMUP_SECONDS, traffic["in_flight"])[2]


@dataclasses.dataclass
class Run:
    """What a run leaves for the metric readers."""
    seconds: float
    setup_s: float
    plan_s: float
    compile_s: float
    compiled_setup: int
    compiled_window: int
    attempted_images: int
    images_done: int
    latencies_s: List[float]
    done: List[Done] = dataclasses.field(repr=False)
    meta: list = dataclasses.field(repr=False)


def run(cfg: dict, traffic: dict, seed: int, seconds: float, t_start: float,
        counter: Counter, trace_dir: Optional[str] = None, log=print):
    """Set-up, window and check of one run. Returns (Run, check result,
    memory peak in bytes or None)."""
    pcfg = program_config(cfg)
    prep = prepare(cfg, traffic, seed, pcfg)
    t = time.perf_counter()
    compiled = compile_step(prep, pcfg, cfg)
    compile_s = time.perf_counter() - t
    first = warm_up(compiled, prep, traffic)
    # the traced step leaves a large heap of Python objects; frozen, and
    # with the collector off in the window, no collection pauses the loop
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    compiled_setup = counter.compiled()
    log(f"set-up: {setup_s:.3f} s, of it plan {prep.plan_s:.3f} s and "
        f"compile {compile_s:.3f} s; programs compiled {compiled_setup}, "
        f"loaded from the cache {counter.hits}")

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    gc.disable()
    try:
        done, end, n = window(compiled, prep.requests, prep.pick,
                              seconds, traffic["in_flight"],
                              first_step=first)
    finally:
        gc.enable()
        if trace_dir is not None:
            jax.profiler.stop_trace()
    compiled_window = counter.compiled() - compiled_setup
    in_window = [d for d in done if d.t_done <= end]
    batch = traffic["batch"]
    rec = Run(seconds=seconds, setup_s=setup_s, plan_s=prep.plan_s,
              compile_s=compile_s, compiled_setup=compiled_setup,
              compiled_window=compiled_window, attempted_images=n * batch,
              images_done=len(in_window) * batch,
              latencies_s=[d.t_done - d.t_dispatch for d in in_window],
              done=done, meta=prep.meta)
    log(f"window: {n} steps dispatched, {len(in_window)} finished inside "
        f"{seconds} s; programs compiled in the window {compiled_window}")

    peak = memory_peak()
    params, xs = prep.params, prep.xs
    del compiled, prep
    refs = reference_logits(params, xs, cfg, cfg["operand_dtype"],
                            REFERENCE_BLOCK)
    return rec, check(done, rec.meta, refs), peak


def memory_peak() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)


def is_correct(numbers: dict) -> bool:
    """Every number compared within its limit."""
    return all(v <= LIMITS[k] for k, v in numbers.items())


# Limits of the numbers compared, and the readings they were set from
# (PERF.md, "How correct is decided"): logit_gap lies between the largest
# gap of the system over a dozen seeds and the smallest of the control;
# the two counts are exact.
LIMITS = {"logit_gap": 1e-2, "false_alarms": 0, "missed_faults": 0}
