"""The protected continuous-batching serving subsystem: scheduler
bookkeeping, KV-cache decode parity, per-slot fault attribution, plan-
trusted audit escalation, and the sharded multi-device session."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
import repro.core as ft
from repro.core import injection as inj
from repro.models import transformer as M
from repro.serving import (ProtectedSession, SlotScheduler, bucket_for,
                           greedy_reference)

MAX_LEN = 24


@pytest.fixture(scope="module")
def cfg():
    return C.get("smollm-360m-smoke")


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def plan(params, cfg):
    return ft.build_plan(params, cfg, batch=4, seq=MAX_LEN)


def _prompts(cfg, lens, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(lens))
    return [np.asarray(jax.random.randint(k, (n,), 0, cfg.vocab_size))
            for k, n in zip(keys, lens)]


def _head_path(cfg):
    return "embed/table" if cfg.tie_embeddings else "embed/head"


# ---------------------------------------------------------------------------
# scheduler bookkeeping (no device work)
# ---------------------------------------------------------------------------

def test_scheduler_admission_eviction_refill():
    s = SlotScheduler(slots=2, max_len=32)
    reqs = [s.submit(np.arange(4), 8), s.submit(np.arange(6), 8),
            s.submit(np.arange(5), 8)]
    assert all(r is not None for r in reqs)
    placed = s.admit()
    # FIFO into the free slots; third request waits
    assert [(sl, r.id) for sl, r in placed] == [(0, 0), (1, 1)]
    assert s.admit() == [] and s.busy()
    s.evict(1)
    placed = s.admit()
    assert [(sl, r.id) for sl, r in placed] == [(1, 2)]
    s.evict(0)
    s.evict(1)
    assert not s.busy()
    # prompts that cannot fit the cache are dropped, not queued
    assert s.submit(np.arange(32), 1) is None
    assert len(s.dropped) == 1 and not s.busy()


def test_scheduler_same_step_evict_then_refill():
    """The edge the async refill path leans on hardest: a slot freed by
    eviction (EOS or KV-capacity) must be claimable by a queued request
    within the SAME scheduler tick, through both the FIFO admit() path
    (sync session) and the direct place() path (async driver)."""
    s = SlotScheduler(slots=2, max_len=32)
    r = [s.submit(np.arange(4), 8) for _ in range(4)]
    s.admit()
    s.evict(0)                     # EOS eviction
    s.evict(1)                     # KV-capacity (max_len) eviction
    placed = s.admit()             # same tick: both freed slots refill FIFO
    assert [(sl, q.id) for sl, q in placed] == [(0, r[2].id), (1, r[3].id)]
    assert s.active[0] is r[2] and s.active[1] is r[3]

    s2 = SlotScheduler(slots=1, max_len=32)
    a, ok = s2.make_request(np.arange(4), 8)
    assert ok and s2.place(a) == 0
    b, ok = s2.make_request(np.arange(4), 8)
    assert ok and s2.place(b) is None      # every slot occupied
    assert s2.evict(0) is a
    assert s2.place(b) == 0                # claimable in the same tick
    # make_request never queues: dropped prompts are recorded, not queued
    c, ok = s2.make_request(np.arange(64), 1)
    assert not ok and c in s2.dropped and not s2.queue


def test_scheduler_buckets():
    assert bucket_for(5, 64) == 8
    assert bucket_for(8, 64) == 8
    assert bucket_for(9, 64) == 16
    assert bucket_for(40, 48) == 48      # clamped to max_len, >= plen
    assert bucket_for(5, 64, exact=True) == 5   # ssm/rec: no padding
    rec_cfg = C.get("smollm-360m-smoke").replace(
        stage_pattern=("rec", "ffn"))
    assert SlotScheduler(2, 64, cfg=rec_cfg).exact_prefill


# ---------------------------------------------------------------------------
# decode-path numerics (launch/steps.py + vector positions)
# ---------------------------------------------------------------------------

def test_kv_cache_decode_matches_full_forward(params, cfg):
    """Prefill->decode greedy continuation must equal re-running the full
    sequence through the forward at every step (the KV cache is a pure
    optimization)."""
    from repro.launch.steps import make_prefill_step, make_serve_step
    plen, gen = 6, 4
    prompts = jnp.asarray(np.stack(_prompts(cfg, (plen, plen), seed=3)))
    max_len = plen + gen

    prefill_fn = jax.jit(make_prefill_step(cfg, max_len))
    serve_fn = jax.jit(make_serve_step(cfg))
    out = prefill_fn(params, {"tokens": prompts})
    nxt = jnp.argmax(out["logits"], -1).astype(jnp.int32)
    caches, positions = out["caches"], jnp.asarray(plen, jnp.int32)
    got = [np.asarray(nxt)]
    for _ in range(gen - 1):
        out = serve_fn(params, {"tokens": nxt, "positions": positions,
                                "caches": caches})
        caches, positions = out["caches"], out["positions"]
        nxt = out["next_tokens"]
        got.append(np.asarray(nxt))
    got = np.concatenate(got, axis=1)                    # (B, gen)

    cur = prompts
    want = []
    for _ in range(gen):
        logits, _, _ = M.forward_train(params, cur, cfg)
        step = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        want.append(np.asarray(step))
        cur = jnp.concatenate([cur, step], axis=1)
    want = np.concatenate(want, axis=1)
    assert np.array_equal(got, want)


def test_vector_positions_match_scalar_decode(params, cfg):
    """decode_step with a (B,) position vector of equal entries must
    reproduce the synchronized scalar-position step (same cache writes,
    same mask rows)."""
    plen = 6
    prompts = jnp.asarray(np.stack(_prompts(cfg, (plen, plen), seed=4)))
    logits, _, caches = M.prefill(params, prompts, cfg, MAX_LEN)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)

    l_s, _, c_s = M.decode_step(params, nxt, caches,
                                jnp.asarray(plen, jnp.int32), cfg)
    l_v, _, c_v = M.decode_step(params, nxt, caches,
                                jnp.full((2,), plen, jnp.int32), cfg)
    assert np.array_equal(np.argmax(np.asarray(l_s), -1),
                          np.argmax(np.asarray(l_v), -1))
    np.testing.assert_allclose(np.asarray(l_s, np.float32),
                               np.asarray(l_v, np.float32),
                               rtol=2e-2, atol=2e-2)
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the session: clean traffic, refill, parity
# ---------------------------------------------------------------------------

def test_session_mixed_prompts_clean_parity(params, cfg, plan):
    """More requests than slots, mixed prompt lengths: every request's
    token stream through the deferred protected session must equal the
    unbatched *unprotected* greedy forward (token-exact), with zero
    faults and zero drops."""
    gen = 4
    prompts = _prompts(cfg, (5, 8, 6, 11))
    sess = ProtectedSession(params, cfg, plan, slots=2, max_len=MAX_LEN)
    rids = [sess.submit(p, max_new_tokens=gen) for p in prompts]
    report = sess.run()

    assert report["counters"]["dropped"] == 0
    assert report["counters"]["faults_detected"] == 0
    assert report["completed"] == len(prompts)
    ucfg = cfg.replace(abft=False)
    for rid, p in zip(rids, prompts):
        want = greedy_reference(params, ucfg, p, gen, MAX_LEN)
        assert sess.tokens_for(rid) == want, f"request {rid} diverged"
    # SLO fields populated
    recs = {r["id"]: r for r in report["requests"]}
    for rid in rids:
        r = recs[rid]
        assert r["ttft_s"] is not None and r["completed_at"] is not None
        assert r["tokens_generated"] == gen
        assert r["finish_reason"] == "length"
    # the two late requests were admitted by refill after evictions
    assert {recs[rids[2]]["slot"], recs[rids[3]]["slot"]} <= {0, 1}


def test_session_eos_eviction(params, cfg, plan):
    """A request whose eos fires stops early and frees its slot."""
    gen = 6
    p = _prompts(cfg, (5,))[0]
    ucfg = cfg.replace(abft=False)
    stream = greedy_reference(params, ucfg, p, gen, MAX_LEN)
    eos = stream[2]        # some token the clean stream really emits
    sess = ProtectedSession(params, cfg, plan, slots=1, max_len=MAX_LEN)
    rid = sess.submit(p, max_new_tokens=gen, eos_id=int(eos))
    report = sess.run()
    rec = {r["id"]: r for r in report["requests"]}[rid]
    assert rec["finish_reason"] == "eos"
    # the session stops at the FIRST occurrence (may precede stream[2])
    cut = stream.index(eos) + 1
    assert sess.tokens_for(rid) == stream[:cut]


# ---------------------------------------------------------------------------
# fault drills: per-slot attribution
# ---------------------------------------------------------------------------

def test_session_decode_fault_localized_to_slot(params, cfg, plan):
    """A decode-step fault injected into ONE slot's logits row must be
    detected, corrected, and attributed to exactly that request - and
    every request's tokens still match the clean reference."""
    slots, target, gen = 2, 1, 4
    head = _head_path(cfg)

    def hook(o):
        # static shapes at trace time: decode = (slots, 1, V) rows
        if o.ndim == 3 and o.shape[0] == slots and o.shape[1] == 1:
            return o.at[target, 0, 3].add(jnp.asarray(1e4, o.dtype))
        return o

    prompts = _prompts(cfg, (5, 8))
    sess = ProtectedSession(params, cfg, plan, slots=slots,
                            max_len=MAX_LEN)
    rids = [sess.submit(p, max_new_tokens=gen) for p in prompts]
    with inj.fault_scope(head, hook):
        report = sess.run()

    recs = {r["id"]: r for r in report["requests"]}
    by_slot = {recs[r]["slot"]: recs[r] for r in rids}
    assert by_slot[target]["faults_detected"] >= 1
    assert by_slot[target]["corrections_applied"] >= 1
    assert by_slot[target]["residuals"] == 0
    assert by_slot[1 - target]["faults_detected"] == 0
    assert report["counters"]["faults_unattributed"] == 0
    ucfg = cfg.replace(abft=False)
    for rid, p in zip(rids, prompts):
        assert sess.tokens_for(rid) == greedy_reference(
            params, ucfg, p, gen, MAX_LEN)


def test_session_prefill_fault_attributed_to_request(params, cfg, plan):
    """A prefill-only fault (sequence dim > 1 at trace time) lands in the
    admitted request's prefill_detected ledger."""
    head = _head_path(cfg)

    def hook(o):
        if o.ndim == 3 and o.shape[0] == 1 and o.shape[1] > 1:
            return o.at[0, 0, 0].add(jnp.asarray(1e4, o.dtype))
        return o

    prompts = _prompts(cfg, (5, 8))
    sess = ProtectedSession(params, cfg, plan, slots=2, max_len=MAX_LEN)
    rids = [sess.submit(p, max_new_tokens=2) for p in prompts]
    with inj.fault_scope(head, hook):
        report = sess.run()
    recs = {r["id"]: r for r in report["requests"]}
    for rid in rids:
        assert recs[rid]["prefill_detected"] == 1
        assert recs[rid]["faults_detected"] >= 1
    assert report["counters"]["faults_detected"] >= 2


# ---------------------------------------------------------------------------
# plan-trusted weight audits on the session cadence
# ---------------------------------------------------------------------------

def _audited_entry(plan):
    return next(n for n, e in plan.entries.items()
                if n.startswith("stages/") and e.wlc is not None)


def _corrupt(params, plan, flips=1):
    """Flip `flips` weight elements of a weight the plan checksums: flip
    i lands at index (i,)*ndim, so two flips hit distinct rows AND
    columns - beyond the single-block in-place repair contract."""
    name = _audited_entry(plan)
    bad = jax.tree.map(lambda x: x, params)   # fresh dict containers
    parts = name.split("/")
    parent = bad
    for part in parts[:-1]:
        parent = parent[part]
    leaf = parent[parts[-1]]
    w = leaf["w"] if isinstance(leaf, dict) else leaf
    for i in range(flips):
        w = w.at[(i,) * w.ndim].add(jnp.asarray(977.0, w.dtype))
    if isinstance(leaf, dict):
        leaf["w"] = w
    else:
        parent[parts[-1]] = w
    return bad


def test_session_audit_refuses_corrupt_weights(params, cfg, plan):
    from repro.runtime.ft import WeightDivergenceError
    sess = ProtectedSession(_corrupt(params, plan, flips=2), cfg, plan,
                            slots=1, max_len=MAX_LEN, audit_every=1)
    sess.submit(_prompts(cfg, (5,))[0], max_new_tokens=2)
    with pytest.raises(WeightDivergenceError):
        sess.run()


def test_session_audit_restores_and_serves(params, cfg, plan):
    """Multi-block damage (two flips) sits beyond the in-place repair
    rung, so the ladder escalates to the checkpoint restore."""
    sess = ProtectedSession(_corrupt(params, plan, flips=2), cfg, plan,
                            slots=1, max_len=MAX_LEN, audit_every=1,
                            restore_fn=lambda: params)
    p = _prompts(cfg, (5,))[0]
    rid = sess.submit(p, max_new_tokens=3)
    report = sess.run()
    assert report["counters"]["weight_restores"] == 1
    assert report["counters"]["weight_repairs"] == 0
    assert report["counters"]["weight_audits"] >= 2   # restore re-audits
    rec = {r["id"]: r for r in report["requests"]}[rid]
    # post-restore audits run with the request active and record verdicts
    assert "clean" in rec["audit_verdicts"]
    ucfg = cfg.replace(abft=False)
    assert sess.tokens_for(rid) == greedy_reference(params, ucfg, p, 3,
                                                    MAX_LEN)


def test_session_mid_stream_repair_keeps_serving(params, cfg, plan):
    """The acceptance scenario: a single weight element flips while a
    request is mid-stream. The next audit solves the block in place from
    the plan's locator sums - no restore, no dropped request - and the
    token stream stays bitwise the clean reference because the repair
    (f64 locator solve, bitwise for f32 leaves) lands before any forward
    runs on the corrupted weights."""
    gen = 6
    p = _prompts(cfg, (5,))[0]
    name = _audited_entry(plan)
    sess = ProtectedSession(params, cfg, plan, slots=1, max_len=MAX_LEN,
                            audit_every=1)
    rid = sess.submit(p, max_new_tokens=gen)
    for _ in range(2):
        assert sess.step()           # prefill + decode on clean weights
    sess.params = _corrupt(sess.params, plan)    # hits `name`
    while sess.step():
        pass
    report = sess.stats.report()
    assert report["counters"]["weight_repairs"] == 1
    assert report["counters"]["weight_restores"] == 0
    assert report["counters"]["dropped"] == 0
    assert report["mttr_repair_s"] is not None
    assert report["mttr_repair_s"] > 0
    rec = {r["id"]: r for r in report["requests"]}[rid]
    assert "repaired" in rec["audit_verdicts"]
    assert rec["finish_reason"] == "length"
    # the repaired leaf is bitwise the pre-corruption original
    np.testing.assert_array_equal(
        np.asarray(ft.weight_leaf(sess.params, name)),
        np.asarray(ft.weight_leaf(params, name)))
    ucfg = cfg.replace(abft=False)
    assert sess.tokens_for(rid) == greedy_reference(params, ucfg, p, gen,
                                                    MAX_LEN)


# ---------------------------------------------------------------------------
# the sharded session (4 emulated devices, subprocess: conftest strips
# XLA_FLAGS so in-process meshes are single-device)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, %r)
    import json
    import jax, jax.numpy as jnp
    import numpy as np

    import repro.configs as C
    import repro.core as ft
    from repro.models import transformer as M
    from repro.serving import ProtectedSession, greedy_reference

    assert jax.device_count() == 4, jax.device_count()
    # untied head: 'embed/head' is a non-scanned checksummed matmul, so the
    # transposed-weight sharding rule has a real target to partition
    # (scanned-stage checksum stacks deliberately replicate - see
    # runtime/sharding.checksum_shardings)
    cfg = C.get("smollm-360m-smoke").replace(tie_embeddings=False)
    max_len, gen = 24, 4
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    plan = ft.build_plan(params, cfg, batch=4, seq=max_len)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(2, 2)

    sess = ProtectedSession(params, cfg, plan, slots=4, max_len=max_len,
                            mesh=mesh, audit_every=4)
    sharded = [n for n, e in sess.plan.entries.items()
               if e.wck is not None and hasattr(e.wck, "cw1")
               and any(ax is not None for ax in e.wck.cw1.sharding.spec)]

    lens = (5, 8, 6, 11, 4, 9)
    keys = jax.random.split(jax.random.PRNGKey(1), len(lens))
    prompts = [np.asarray(jax.random.randint(k, (n,), 0, cfg.vocab_size))
               for k, n in zip(keys, lens)]
    rids = [sess.submit(p, max_new_tokens=gen) for p in prompts]
    report = sess.run()

    ucfg = cfg.replace(abft=False)
    parity = all(sess.tokens_for(rid) == greedy_reference(
                     params, ucfg, p, gen, max_len)
                 for rid, p in zip(rids, prompts))
    print(json.dumps({
        "devices": jax.device_count(),
        "sharded_checksums": len(sharded),
        "completed": report["completed"],
        "dropped": report["counters"]["dropped"],
        "faults": report["counters"]["faults_detected"],
        "audits": report["counters"]["weight_audits"],
        "parity": parity}))
""")


@pytest.mark.slow
def test_session_on_four_device_mesh():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _MESH_SCRIPT % (os.path.abspath(src),)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["devices"] == 4
    assert data["sharded_checksums"] >= 1, data
    assert data["completed"] == 6 and data["dropped"] == 0, data
    assert data["faults"] == 0 and data["audits"] >= 1, data
    assert data["parity"], data
