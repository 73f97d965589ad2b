"""Protected serving benchmark: continuous-batching throughput with the
deferred ProtectedModel path + plan-trusted weight audits vs the same
session with protection off entirely (``abft=False``, no plan).

One mixed-prompt workload (more requests than slots, staggered lengths)
runs through both sessions; each mode reports wall time, tok/s and
ttft p50/p95 from the ServingStats report, and every request's token
stream is checked bitwise against ``greedy_reference`` - the unbatched,
unprotected forward - so the protected column's numbers are only
credited when its outputs are exactly the clean ones. ``BENCH_serve.json``
carries a gate CI asserts on: zero dropped requests and clean-traffic
parity in BOTH modes (the protected-vs-unprotected overhead itself is
informational - CPU smoke scales sit on the dispatch floor, not the
paper's compute-bound regime).

On a >=4-device host (CI sets XLA_FLAGS=--xla_force_host_platform_\
device_count=4) both sessions run on a (2,2) (data, model) mesh, so the
gate also covers ``ProtectionPlan.shard``'s checksum placement.

The artifact also carries a ``repair`` section: the audit ladder's two
remedies timed head-to-head on the same model tree - in-place repair of a
single flipped weight element from the plan's locator sums vs a full
checkpoint restore (params read back from an npz on disk) forced by
multi-block damage. Both paths pay the same audit bookends, so the delta
is repair math vs checkpoint bandwidth; the gate asserts the in-place
rung is never slower than the restore it replaces.

v2 adds the async-driver cells: a **Poisson open-loop load sweep**
(exponential inter-arrivals at 0.5x/1.0x/2.0x of the measured warm
service rate) runs identical arrival schedules through the
``ServingDriver`` and through a synchronous ``ProtectedSession`` step
loop, recording queue-delay + TTFT percentiles per arrival rate; and a
**driver mid-stream repair cell** that corrupts a weight while a request
streams and measures that admission keeps answering (submit latency
while the repair is pending) with zero timeout finishes. The gate grows
matching clauses: zero driver drops, driver clean parity, zero driver
false positives, driver TTFT <= synchronous TTFT (small noise slack) at
the saturating rate, and ``weight_repairs >= 1`` in the repair cell.

    PYTHONPATH=src python -m benchmarks.run --only serve
    REPRO_BENCH_SERVE_JSON=/tmp/s.json ... (override the artifact path)
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.core import build_plan, weight_leaf
from repro.models import transformer as M
from repro.runtime.ft import PlanAuditor, set_weight_leaf
from repro.serving import (ProtectedSession, ServingDriver,
                           greedy_reference)
from .common import row

SCHEMA = "repro.bench_serve/v2"
ARCH = "smollm-360m-smoke"
SLOTS = 4
MAX_LEN = 24
GEN = 4
PROMPT_LENS = (5, 8, 6, 11, 4, 9)
AUDIT_EVERY = 4
SWEEP_REQS = 12                     # requests per arrival-rate wave
SWEEP_RATES = (0.5, 1.0, 2.0)       # offered load, x the warm service rate
TTFT_SLACK = 1.10                   # CPU-smoke timing noise allowance


def _prompts(cfg, lens, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lens]


def _run_mode(params, cfg, plan, prompts, mesh, refs) -> dict:
    sess = ProtectedSession(params, cfg, plan, slots=SLOTS,
                            max_len=MAX_LEN, mesh=mesh,
                            audit_every=AUDIT_EVERY if plan is not None
                            else 0)
    # cold pass compiles the decode program + every prefill bucket; the
    # same workload then re-runs warm, and the throughput columns come
    # from the warm-pass deltas (a cold wall_s is ~all XLA compile time)
    rids1 = [sess.submit(p, max_new_tokens=GEN) for p in prompts]
    rep1 = sess.run()
    rids2 = [sess.submit(p, max_new_tokens=GEN) for p in prompts]
    rep2 = sess.run()
    parity = [sess.tokens_for(rid) == refs[i % len(refs)]
              for i, rid in enumerate(rids1 + rids2)]
    warm_wall = rep2["wall_s"] - rep1["wall_s"]
    warm_toks = rep2["tokens_total"] - rep1["tokens_total"]
    by_id = {r["id"]: r for r in rep2["requests"]}
    warm_ttfts = sorted(by_id[r]["ttft_s"] for r in rids2
                        if by_id[r]["ttft_s"] is not None)
    return {
        "correction": sess.correction,
        "audited": plan is not None,
        "cold_wall_s": rep1["wall_s"],
        "wall_s": warm_wall,
        "tok_per_s": warm_toks / warm_wall if warm_wall > 0 else None,
        "ttft_p50_s": warm_ttfts[len(warm_ttfts) // 2]
        if warm_ttfts else None,
        "ttft_p95_s": warm_ttfts[-1] if warm_ttfts else None,
        "completed": rep2["completed"],
        "tokens_total": rep2["tokens_total"],
        "dropped": rep2["counters"]["dropped"],
        "faults_detected": rep2["counters"]["faults_detected"],
        "weight_audits": rep2["counters"]["weight_audits"],
        "weight_repairs": rep2["counters"]["weight_repairs"],
        "clean_parity": all(parity),
        "parity_per_request": parity,
    }


def _with_flips(params, name, idxs, delta: float = 977.0):
    leaf = weight_leaf(params, name)
    arr = np.asarray(leaf).copy()
    for idx in idxs:
        arr[idx] += delta
    return set_weight_leaf(params, name, jnp.asarray(arr))


def _repair_restore_drill(params, plan, reps: int = 3) -> dict:
    """MTTR head-to-head for the audit ladder's two remedies. The restore
    path reads the whole param tree back from an npz checkpoint on disk
    (honest restore bandwidth, not a no-op lambda); the repair path
    solves the corrupted block in place from the plan's float64 locator
    sums. Both go through PlanAuditor.audit_or_restore, so each timing
    includes the triggering audit and the verifying re-audit."""
    flat, treedef = jax.tree_util.tree_flatten(params)
    ckpt = tempfile.NamedTemporaryFile(suffix=".npz", delete=False)
    ckpt.close()
    np.savez(ckpt.name, **{f"a{i}": np.asarray(x)
                           for i, x in enumerate(flat)})

    def restore_fn():
        data = np.load(ckpt.name)
        leaves = [jnp.asarray(data[f"a{i}"]) for i in range(len(flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    name = next(n for n, e in plan.entries.items() if e.wlc is not None)
    nd = np.asarray(weight_leaf(params, name)).ndim
    single = [(0,) * nd]
    multi = [(0,) * nd, (1,) * nd]   # two blocks / two rows+cols: beyond
    #                                  the single-block repair contract
    repair_s, restore_s, verdicts = [], [], []
    for _ in range(reps):
        for idxs, bucket in ((single, repair_s), (multi, restore_s)):
            auditor = PlanAuditor(plan, restore_fn=restore_fn,
                                  params_fn=lambda s: s)
            bad = _with_flips(params, name, idxs)
            t0 = time.perf_counter()
            fixed = auditor.audit_or_restore(bad)
            jax.block_until_ready(fixed)
            bucket.append(time.perf_counter() - t0)
            verdicts.append(auditor.last_verdict)
    os.unlink(ckpt.name)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    want = ["repaired", "restored"] * reps
    return {
        "entry": name,
        "repair_s": med(repair_s),
        "restore_s": med(restore_s),
        "repair_samples_s": repair_s,
        "restore_samples_s": restore_s,
        "verdicts": verdicts,
        "verdicts_ok": verdicts == want,
    }


# ---------------------------------------------------------------------------
# the async driver: Poisson open-loop load sweep + mid-stream repair
# ---------------------------------------------------------------------------

def _poisson_arrivals(rate_rps: float, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))


def _wave_stats(report: dict, rids, wall_s: float) -> dict:
    by = {r["id"]: r for r in report["requests"]}
    recs = [by[r] for r in rids]

    def pct(field, q):
        xs = sorted(r[field] for r in recs if r[field] is not None)
        if not xs:
            return None
        return xs[min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))]

    return {
        "completed": sum(r["completed_at"] is not None for r in recs),
        "wall_s": wall_s,
        "queue_delay_p50_s": pct("queue_delay_s", 0.50),
        "queue_delay_p95_s": pct("queue_delay_s", 0.95),
        "ttft_p50_s": pct("ttft_s", 0.50),
        "ttft_p95_s": pct("ttft_s", 0.95),
        "ttft_p99_s": pct("ttft_s", 0.99),
    }


def _driver_wave(driver, prompts, arrivals) -> tuple:
    """Open-loop client: submit each request at its Poisson arrival time
    (never waiting for responses), then drain."""
    rids = []
    t0 = time.perf_counter()
    for p, at in zip(prompts, arrivals):
        delay = at - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        v = driver.submit(p, max_new_tokens=GEN)
        rids.append(v.rid)
    report = driver.drain()
    return rids, report, time.perf_counter() - t0


def _sync_wave(sess, prompts, arrivals) -> tuple:
    """The same open-loop schedule against the synchronous session: the
    step loop IS the server, so arrivals due between steps are submitted
    between steps - admission shares the host loop with decode, which is
    exactly the cost the driver removes."""
    rids = []
    i, n = 0, len(prompts)
    t0 = time.perf_counter()
    while i < n or sess.scheduler.busy():
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            rids.append(sess.submit(prompts[i], max_new_tokens=GEN))
            i += 1
        if sess.scheduler.busy():
            sess.step()
        elif i < n:
            time.sleep(max(arrivals[i] - (time.perf_counter() - t0), 0))
    return rids, sess.stats.report(), time.perf_counter() - t0


def _load_sweep(params, cfg, plan, prompts, refs, mesh) -> dict:
    """Sweep offered load over identical Poisson schedules through the
    async driver and the synchronous session. Rates are calibrated
    against the driver's measured warm closed-loop service rate so the
    sweep lands at genuinely sub-/at-/over-saturating points on any
    host speed."""
    n = SWEEP_REQS
    wave_p = [prompts[i % len(prompts)] for i in range(n)]
    wave_refs = [refs[i % len(refs)] for i in range(n)]

    driver = ServingDriver(params, cfg, plan, slots=SLOTS,
                           max_len=MAX_LEN, mesh=mesh,
                           queue_capacity=4 * n)
    sess = ProtectedSession(params, cfg, plan, slots=SLOTS,
                            max_len=MAX_LEN, mesh=mesh)
    try:
        # closed-loop warmup compiles both instances AND measures the
        # warm service rate the sweep rates are multiples of
        for p in wave_p:
            driver.submit(p, max_new_tokens=GEN)
        driver.drain()
        t0 = time.perf_counter()
        for p in wave_p:
            driver.submit(p, max_new_tokens=GEN)
        driver.drain()
        service_rps = n / (time.perf_counter() - t0)
        for p in wave_p:
            sess.submit(p, max_new_tokens=GEN)
        sess.run()

        waves, parity, all_rids_d = [], [], []
        for wi, mult in enumerate(SWEEP_RATES):
            rate = mult * service_rps
            arrivals = _poisson_arrivals(rate, n, seed=100 + wi)
            d_rids, d_rep, d_wall = _driver_wave(driver, wave_p, arrivals)
            s_rids, s_rep, s_wall = _sync_wave(sess, wave_p, arrivals)
            all_rids_d.extend(d_rids)
            parity.extend(driver.tokens_for(r) == wave_refs[i % len(wave_refs)]
                          for i, r in enumerate(d_rids))
            parity.extend(sess.tokens_for(r) == wave_refs[i % len(wave_refs)]
                          for i, r in enumerate(s_rids))
            waves.append({
                "rate_mult": mult,
                "rate_rps": rate,
                "saturating": mult >= max(SWEEP_RATES),
                "driver": _wave_stats(d_rep, d_rids, d_wall),
                "sync": _wave_stats(s_rep, s_rids, s_wall),
            })
        d_rep_final = driver.drain()
        s_rep_final = sess.stats.report()
    finally:
        driver.close()

    sat = next(w for w in waves if w["saturating"])
    d_ttft, s_ttft = sat["driver"]["ttft_p50_s"], sat["sync"]["ttft_p50_s"]
    return {
        "service_rate_rps": service_rps,
        "requests_per_wave": n,
        "waves": waves,
        "clean_parity": all(parity),
        "driver_dropped": d_rep_final["counters"]["dropped"],
        "driver_rejected": d_rep_final["counters"]["rejected"],
        "driver_timeouts": d_rep_final["counters"]["timeouts"],
        "driver_faults_detected":
            d_rep_final["counters"]["faults_detected"],
        "sync_faults_detected": s_rep_final["counters"]["faults_detected"],
        "saturating_ttft_driver_s": d_ttft,
        "saturating_ttft_sync_s": s_ttft,
        "driver_ttft_le_sync": bool(
            d_ttft is not None and s_ttft is not None
            and d_ttft <= s_ttft * TTFT_SLACK),
    }


def _driver_repair_cell(params, cfg, plan, prompts, refs, mesh) -> dict:
    """Mid-stream repair under the driver: corrupt one weight element
    while a request streams, keep submitting while the controller's
    audit solves the block, and check nobody stalls - the ISSUE's
    'repair never gates admission' claim as a measured number."""
    driver = ServingDriver(params, cfg, plan, slots=SLOTS,
                           max_len=MAX_LEN, mesh=mesh, audit_every=1)
    name = next(n for n, e in plan.entries.items()
                if n.startswith("stages/") and e.wlc is not None)
    nd = np.asarray(weight_leaf(params, name)).ndim
    try:
        for p in prompts:                      # warm compile
            driver.submit(p, max_new_tokens=GEN)
        driver.drain()

        v0 = driver.submit(prompts[0], max_new_tokens=GEN)
        t0 = time.monotonic()
        while driver.tokens_generated(v0.rid) < 1:
            if time.monotonic() - t0 > 120:
                raise RuntimeError("repair cell: no mid-stream progress")
            time.sleep(0.001)
        submit_lat = []
        with driver.paused():
            driver.params = _with_flips(driver.params, name, [(0,) * nd])
            # admission answers while corrupted weights await the audit
            extra = []
            for p in prompts[1:3]:
                ts = time.perf_counter()
                extra.append(driver.submit(p, max_new_tokens=GEN))
                submit_lat.append(time.perf_counter() - ts)
        report = driver.drain()
        rids = [v0.rid] + [v.rid for v in extra]
        parity = [driver.tokens_for(r) == refs[i % len(refs)]
                  for i, r in enumerate(rids)]
    finally:
        driver.close()
    return {
        "entry": name,
        "weight_repairs": report["counters"]["weight_repairs"],
        "weight_restores": report["counters"]["weight_restores"],
        "timeouts": report["counters"]["timeouts"],
        "completed": report["completed"],
        "mttr_repair_s": report["mttr_repair_s"],
        "submit_while_corrupt_max_s": max(submit_lat),
        "clean_parity": all(parity),
        "ok": bool(report["counters"]["weight_repairs"] >= 1
                   and report["counters"]["weight_restores"] == 0
                   and report["counters"]["timeouts"] == 0
                   and all(parity)),
    }


def run(out_path: str | None = None):
    print("# serve: protected continuous batching (deferred + plan audit) "
          "vs unprotected session")
    out_path = out_path or os.environ.get("REPRO_BENCH_SERVE_JSON",
                                          "BENCH_serve.json")
    # untied head so the sharded plan has a genuinely partitioned
    # checksum entry on the mesh path (scanned-stage stacks replicate by
    # design - runtime/sharding.checksum_shardings)
    cfg = C.get(ARCH).replace(tie_embeddings=False)
    ucfg = cfg.replace(abft=False)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg, PROMPT_LENS)

    mesh = None
    if jax.device_count() >= 4:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 2)

    # the parity oracle: unbatched, unprotected greedy continuation
    refs = [greedy_reference(params, ucfg, p, GEN, MAX_LEN)
            for p in prompts]

    plan = build_plan(params, cfg, batch=SLOTS, seq=MAX_LEN)
    protected = _run_mode(params, cfg, plan, prompts, mesh, refs)
    unprotected = _run_mode(params, ucfg, None, prompts, mesh, refs)
    repair = _repair_restore_drill(params, plan)
    sweep = _load_sweep(params, cfg, plan, prompts, refs, mesh)
    driver_repair = _driver_repair_cell(params, cfg, plan, prompts, refs,
                                        mesh)

    over = None
    if unprotected["tok_per_s"] and protected["tok_per_s"]:
        over = (unprotected["tok_per_s"] / protected["tok_per_s"] - 1) * 100

    gate = {
        "dropped": protected["dropped"] + unprotected["dropped"],
        "clean_parity": bool(protected["clean_parity"]
                             and unprotected["clean_parity"]),
        "false_positives": protected["faults_detected"],
        "repair_le_restore": bool(repair["repair_s"]
                                  <= repair["restore_s"]),
        "repair_verdicts_ok": bool(repair["verdicts_ok"]),
        "driver_dropped": sweep["driver_dropped"],
        "driver_clean_parity": bool(sweep["clean_parity"]),
        "driver_false_positives": sweep["driver_faults_detected"],
        "driver_ttft_le_sync": bool(sweep["driver_ttft_le_sync"]),
        "driver_repair_ok": bool(driver_repair["ok"]),
        "pass": bool(protected["dropped"] == 0
                     and unprotected["dropped"] == 0
                     and protected["clean_parity"]
                     and unprotected["clean_parity"]
                     and protected["faults_detected"] == 0
                     and repair["repair_s"] <= repair["restore_s"]
                     and repair["verdicts_ok"]
                     and sweep["driver_dropped"] == 0
                     and sweep["driver_rejected"] == 0
                     and sweep["driver_timeouts"] == 0
                     and sweep["clean_parity"]
                     and sweep["driver_faults_detected"] == 0
                     and sweep["sync_faults_detected"] == 0
                     and sweep["driver_ttft_le_sync"]
                     and driver_repair["ok"]),
    }
    doc = {
        "schema": SCHEMA,
        "meta": {"arch": ARCH, "slots": SLOTS, "max_len": MAX_LEN,
                 "gen": GEN, "prompt_lens": list(PROMPT_LENS),
                 "sweep_reqs": SWEEP_REQS,
                 "sweep_rates": list(SWEEP_RATES),
                 "ttft_slack": TTFT_SLACK,
                 "devices": jax.device_count(),
                 "mesh": list(mesh.devices.shape) if mesh is not None
                 else None,
                 "jax_version": jax.__version__},
        "protected": protected,
        "unprotected": unprotected,
        "repair": repair,
        "load_sweep": sweep,
        "driver_repair": driver_repair,
        "throughput_overhead_pct": over,
        "gate": gate,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    sat = next(w for w in sweep["waves"] if w["saturating"])
    print(f"# wrote {out_path} (gate pass={gate['pass']}; "
          f"protected {protected['tok_per_s'] or 0:.1f} tok/s vs "
          f"unprotected {unprotected['tok_per_s'] or 0:.1f} tok/s; "
          f"repair {repair['repair_s'] * 1e3:.1f} ms vs restore "
          f"{repair['restore_s'] * 1e3:.1f} ms; saturating ttft "
          f"driver {(sat['driver']['ttft_p50_s'] or 0) * 1e3:.1f} ms vs "
          f"sync {(sat['sync']['ttft_p50_s'] or 0) * 1e3:.1f} ms)")
    return [
        row("serve/protected", protected["wall_s"] * 1e6,
            f"tok_per_s={protected['tok_per_s'] or 0:.1f};"
            f"parity={int(protected['clean_parity'])};"
            f"dropped={protected['dropped']}"),
        row("serve/unprotected", unprotected["wall_s"] * 1e6,
            f"tok_per_s={unprotected['tok_per_s'] or 0:.1f};"
            f"parity={int(unprotected['clean_parity'])};"
            f"dropped={unprotected['dropped']}"),
        row("serve/weight_repair", repair["repair_s"] * 1e6,
            f"restore_us={repair['restore_s'] * 1e6:.0f};"
            f"verdicts_ok={int(repair['verdicts_ok'])}"),
        row("serve/driver_saturated", (sat["driver"]["ttft_p50_s"] or 0)
            * 1e6,
            f"sync_ttft_us={(sat['sync']['ttft_p50_s'] or 0) * 1e6:.0f};"
            f"parity={int(sweep['clean_parity'])};"
            f"dropped={sweep['driver_dropped']}"),
        row("serve/driver_repair",
            (driver_repair["mttr_repair_s"] or 0) * 1e6,
            f"submit_max_us="
            f"{driver_repair['submit_while_corrupt_max_s'] * 1e6:.0f};"
            f"ok={int(driver_repair['ok'])}"),
    ]


if __name__ == "__main__":
    run()
