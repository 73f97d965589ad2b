"""ProtectedSession: continuous-batching serving through the deferred
ProtectedModel path.

One decode program is compiled for a fixed (slots, 1) token shape and
never recompiled: the slot scheduler admits queued requests into free
slots, each admission runs a batch-1 prefill (bucketed prompt shapes, a
traced last-row index) whose caches are inserted into the donated
slot-indexed KV buffers, and eviction on EOS/max-len frees the slot for
the next queued request. Protection is the paper's serving regime end to
end: every forward routes through `ProtectedModel` with
`correction="deferred"` (detect-only hot path + ONE model-level cond),
at-rest weights are audited against the ProtectionPlan's persisted
checksums on a step cadence (runtime.ft.PlanAuditor - the RowHammer
root-of-trust), and `ProtectionPlan.shard(mesh)` places the checksums
with the same rules as their weights so the whole session runs on the
(pod, data, model) mesh.

Fault attribution is per slot: the deferred workflow's detect-pass output
(`with_detect_out=True`) equals the served output bitwise on the clean
path and carries the *uncorrected* values on a corrective rerun, so
comparing the two localizes which slot's logits a correction actually
changed - detection evidence from inactive slots is masked out of the
accounting.

Per-request parity caveat: batch rows are independent through attention
(per-slot positions) and dense FFN, so clean-traffic token streams match
the unbatched forward exactly (`greedy_reference`); MoE blocks couple
rows through expert capacity and void that guarantee.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ProtectedModel, as_fault_report
from repro.models import transformer as M
from repro.runtime.ft import PlanAuditor
from .scheduler import SlotScheduler
from .stats import RequestRecord, ServingStats

F32 = jnp.float32


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


class ProtectedSession:
    """A protected continuous-batching serving session.

        plan = ft.build_plan(params, cfg, batch=slots, seq=max_len)
        sess = ProtectedSession(params, cfg, plan, slots=4, max_len=64)
        rid = sess.submit(prompt_tokens, max_new_tokens=16, eos_id=2)
        report = sess.run()            # drain queue; ServingStats report
        sess.tokens_for(rid)           # generated token ids

    Knobs: `slots` (decode batch width), `max_len` (KV capacity per
    slot), `correction` ("deferred" by default when a plan is present),
    `audit_every` (plan-trusted weight-audit cadence in session steps, 0
    = off; divergence climbs the ladder: in-place repair from the plan's
    locator sums, then restore via `restore_fn`, then
    WeightDivergenceError), `mesh` (params/caches/plan all placed by
    runtime.sharding rules), `slot_tol` (relative tolerance of the
    per-slot correction localizer; clean slots differ by exactly 0).
    """

    def __init__(self, params, cfg, plan=None, *, slots: int = 4,
                 max_len: int = 64, correction: str = "auto",
                 mesh=None, audit_every: int = 0, restore_fn=None,
                 slot_tol: float = 1e-3, bucket_floor: int = 8):
        if correction == "auto":
            correction = "deferred" if plan is not None else "per_layer"
        if correction == "deferred" and plan is None:
            raise ValueError("ProtectedSession: correction='deferred' "
                             "needs a ProtectionPlan")
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.correction = correction
        self.mesh = mesh
        self.audit_every = audit_every
        self.slot_tol = slot_tol

        if mesh is not None:
            from repro.runtime.sharding import (cache_shardings,
                                                param_shardings)
            self._pshard = param_shardings(params, mesh, cfg)
            params = jax.device_put(params, self._pshard)
            if plan is not None:
                plan = plan.shard(mesh, cfg)
            if restore_fn is not None:
                user_restore = restore_fn

                def restore_fn():
                    return jax.device_put(user_restore(), self._pshard)
        self.params = params
        self.plan = plan

        self.scheduler = SlotScheduler(slots, max_len, cfg=cfg,
                                       bucket_floor=bucket_floor)
        self.stats = ServingStats()
        self.auditor = PlanAuditor(plan, restore_fn=restore_fn,
                                   params_fn=lambda s: s,
                                   stats=self.stats.counters)

        with self._ctx():
            caches = M.init_caches(cfg, slots, max_len)
            if mesh is not None:
                from repro.runtime.sharding import cache_shardings
                caches = jax.device_put(
                    caches, cache_shardings(caches, mesh, slots))
        self._caches = caches

        k = cfg.num_codebooks
        self._h_tokens = np.zeros((slots, 1, k) if k else (slots, 1),
                                  np.int32)
        self._h_positions = np.zeros((slots,), np.int32)
        self._t0 = time.perf_counter()
        self._step_count = 0
        self._prefill_fns: Dict[int, Any] = {}
        self._step_fn = self._build_step()
        self._insert_fn = self._build_insert()

    # -- time --------------------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    # -- compiled pieces ---------------------------------------------------
    def _fix_cb(self, nxt):
        if self.cfg.num_codebooks and nxt.ndim == 2:
            nxt = jnp.repeat(nxt[..., None], self.cfg.num_codebooks, -1)
        return nxt

    def _build_step(self):
        pm = ProtectedModel(M.decode_apply(self.cfg), self.plan)
        deferred = self.correction == "deferred"
        tol = self.slot_tol

        def step(params, tokens, caches, positions):
            if deferred:
                (logits, caches2), rep, (logits_d, _) = pm(
                    params, tokens, caches, positions,
                    correction="deferred", with_detect_out=True)
                b = logits.shape[0]
                l32 = logits.astype(F32).reshape(b, -1)
                d32 = logits_d.astype(F32).reshape(b, -1)
                # clean path: cond returned the detect-pass output, diff is
                # exactly 0. Corrective rerun: only rows the ladder touched
                # move, so the argmax localizes the fault to its slot.
                diff = jnp.max(jnp.abs(l32 - d32), axis=-1)
                hit = (diff > tol * (jnp.max(jnp.abs(d32)) + 1.0)
                       ).astype(jnp.int32)
            else:
                (logits, caches2), rep = pm(params, tokens, caches,
                                            positions,
                                            correction=self.correction)
                hit = jnp.zeros((logits.shape[0],), jnp.int32)
            fr = as_fault_report(rep)
            nxt = self._fix_cb(jnp.argmax(logits, -1).astype(jnp.int32))
            return {"next": nxt, "caches": caches2, "hit": hit,
                    "stats": jnp.stack([fr.detected, fr.corrected_by,
                                        fr.residual])}

        return jax.jit(step, donate_argnums=(2,))

    def _prefill(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            pm = ProtectedModel(M.prefill_apply_at(self.cfg, self.max_len),
                                self.plan)

            def pf(params, tokens, last):
                (li, caches), rep = pm(params, tokens, last,
                                       correction=self.correction)
                fr = as_fault_report(rep)
                nxt = self._fix_cb(jnp.argmax(li, -1).astype(jnp.int32))
                return {"next": nxt, "caches": caches,
                        "stats": jnp.stack([fr.detected, fr.corrected_by,
                                            fr.residual])}

            fn = self._prefill_fns[bucket] = jax.jit(pf)
        return fn

    def _build_insert(self):
        def insert(big, small, slot):
            flat_b, tdef = jax.tree_util.tree_flatten_with_path(big)
            flat_s = jax.tree_util.tree_leaves(small)
            out = []
            for (path, b), s in zip(flat_b, flat_s):
                ps = _path_str(path)
                # stacked stage caches carry a leading reps axis; the
                # batch (slot) axis sits behind it
                ax = 1 if (ps.startswith("stages") or "/stages" in ps) \
                    else 0
                starts = [jnp.zeros((), jnp.int32)] * b.ndim
                starts[ax] = jnp.asarray(slot, jnp.int32)
                out.append(jax.lax.dynamic_update_slice(
                    b, s.astype(b.dtype), tuple(starts)))
            return jax.tree_util.tree_unflatten(tdef, out)

        return jax.jit(insert, donate_argnums=(0,))

    # -- request surface ---------------------------------------------------
    def submit(self, tokens, max_new_tokens: int = 16,
               eos_id: Optional[int] = None) -> int:
        """Enqueue one request; returns its id (served on later step()s)."""
        now = self._now()
        req = self.scheduler.submit(tokens, max_new_tokens, eos_id)
        if req is None:
            req = self.scheduler.dropped[-1]
            rec = self.stats.add(RequestRecord(
                req.id, req.prompt_len, req.max_new_tokens))
            rec.submitted_at = now
            rec.finish_reason = "dropped"
            self.stats.counters["dropped"] += 1
            return req.id
        rec = self.stats.add(RequestRecord(req.id, req.prompt_len,
                                           req.max_new_tokens))
        rec.submitted_at = now
        return req.id

    def tokens_for(self, rid: int) -> List:
        return list(self.stats.record(rid).tokens)

    # -- the serving loop --------------------------------------------------
    def _attr(self, rec: RequestRecord, s: np.ndarray,
              prefill: bool = False) -> None:
        """Attribute one (detected, corrected_by, residual) verdict stack
        to a request's ledger (session counters are per-event, kept by
        the callers)."""
        if not int(s[0]):
            return
        rec.faults_detected += 1
        if prefill:
            rec.prefill_detected += 1
        if int(s[1]) > 0:
            rec.corrections_applied += 1
        if int(s[2]):
            rec.residuals += 1

    def _count_event(self, s: np.ndarray) -> None:
        if not int(s[0]):
            return
        self.stats.counters["faults_detected"] += 1
        if int(s[1]) > 0:
            self.stats.counters["faults_corrected"] += 1

    def _finish(self, slot: int, reason: str) -> None:
        req = self.scheduler.evict(slot)
        rec = self.stats.record(req.id)
        rec.completed_at = self._now()
        rec.finish_reason = reason

    def _emit(self, req, tok, next_pos: int) -> Optional[str]:
        """Append one emitted token; returns a finish reason or None.
        `next_pos` is the cache position the NEXT decode write would use
        (continuing is impossible once it reaches max_len)."""
        rec = self.stats.record(req.id)
        rec.tokens.append(int(tok) if np.ndim(tok) == 0 else
                          np.asarray(tok).tolist())
        if (req.eos_id is not None and np.ndim(tok) == 0
                and int(tok) == req.eos_id):
            return "eos"
        if rec.tokens_generated >= req.max_new_tokens:
            return "length"
        if next_pos >= self.max_len:
            return "max_len"
        return None

    def _prep_prefill(self, req):
        """Host-side prefill prep (bucket choice + padded token buffer) -
        pure, so the async driver runs it at submit time, off the runner's
        critical path."""
        plen = req.prompt_len
        bucket = self.scheduler.bucket(plen)
        toks = np.zeros((1, bucket) + req.tokens.shape[1:], np.int32)
        toks[0, :plen] = req.tokens
        return bucket, toks

    def _dispatch_prefill(self, slot: int, req, bucket: int,
                          buf: np.ndarray):
        """Device half of one admission: run the bucketed prefill and
        insert its caches into the slot. Returns the async output dict
        (next/caches/stats still device-resident)."""
        rec = self.stats.record(req.id)
        rec.slot = slot
        rec.admitted_at = self._now()
        with self._ctx():
            out = self._prefill(bucket)(
                self.params, jnp.asarray(buf),
                jnp.asarray(req.prompt_len - 1, jnp.int32))
            self._caches = self._insert_fn(self._caches, out["caches"],
                                           jnp.asarray(slot, jnp.int32))
        self.stats.counters["prefills"] += 1
        return out

    def _apply_prefill_outputs(self, nxt: np.ndarray, s: np.ndarray,
                               slot: int, req):
        """Host half of one admission: attribute the prefill verdict and
        emit the first token. Returns the token when the request keeps
        decoding, None when the prefill already finished it."""
        rec = self.stats.record(req.id)
        self._count_event(s)
        self._attr(rec, s, prefill=True)
        tok = nxt[0, 0]
        rec.first_token_at = self._now()
        reason = self._emit(req, tok, next_pos=req.prompt_len)
        if reason is not None:
            self._finish(slot, reason)
            return None
        return tok

    def _prefill_into(self, slot: int, req) -> None:
        bucket, buf = self._prep_prefill(req)
        out = self._dispatch_prefill(slot, req, bucket, buf)
        tok = self._apply_prefill_outputs(np.asarray(out["next"]),
                                          np.asarray(out["stats"]),
                                          slot, req)
        if tok is None:
            return
        self._h_tokens[slot, 0] = tok
        self._h_positions[slot] = req.prompt_len

    def _run_audit(self) -> str:
        """One plan-trusted weight audit through the full ladder; swaps
        repaired/restored params in and records the verdict on every
        active request's ledger. Returns the verdict."""
        self.params = self.auditor.audit_or_restore(self.params)
        verdict = self.auditor.last_verdict
        if verdict == "repaired":
            # graceful degradation: single-block weight corruption
            # was solved in place mid-session; record the MTTR and
            # keep serving without dropping a request
            self.stats.repair_s.append(self.auditor.last_repair_s)
            if self.mesh is not None:
                # the repaired leaf was rebuilt on the host - put it
                # back under the session's param shardings
                self.params = jax.device_put(self.params, self._pshard)
        for req in self.scheduler.active.values():
            self.stats.record(req.id).audit_verdicts.append(verdict)
        return verdict

    def step(self) -> bool:
        """One scheduler tick: audit cadence, admit+prefill, one decode
        step over all slots. Returns True while work remains."""
        if (self.plan is not None and self.audit_every
                and self._step_count % self.audit_every == 0):
            self._run_audit()
        self._step_count += 1
        self.stats.counters["steps"] += 1

        for slot, req in self.scheduler.admit():
            self._prefill_into(slot, req)

        if self.scheduler.active:
            snap = self._snapshot_active()
            out = self._dispatch_decode(self._h_tokens.copy())
            for slot, _, _ in snap:
                self._h_positions[slot] += 1
            self._apply_decode_outputs(np.asarray(out["next"]),
                                       np.asarray(out["hit"]),
                                       np.asarray(out["stats"]), snap)
        return self.scheduler.busy()

    def _snapshot_active(self):
        """(slot, request, position-after-this-step) for every occupied
        slot - the launch-time view the host bookkeeping later applies
        against (the async driver finalizes a step AFTER newer launches
        have advanced positions and possibly re-assigned slots)."""
        return [(slot, self.scheduler.active[slot],
                 int(self._h_positions[slot]) + 1)
                for slot in self.scheduler.active_slots()]

    def _dispatch_decode(self, tokens):
        """Launch one decode step over all slots (async; `tokens` may be
        host or device-resident). Chains the donated caches."""
        with self._ctx():
            # a host copy: the host array advances right after this
            # async launch, and the CPU backend may read a numpy argument
            # in place (zero-copy) when the step runs - a jnp copy of it
            # is itself an async read of the live array
            out = self._step_fn(self.params, tokens, self._caches,
                                self._h_positions.copy())
        self._caches = out["caches"]
        self.stats.counters["decode_steps"] += 1
        return out

    def _apply_decode_outputs(self, nxt: np.ndarray, hit: np.ndarray,
                              s: np.ndarray, snap) -> None:
        """Host half of one decode step: fault attribution + token
        emission + EOS/length eviction, against the launch-time snapshot.
        Slots whose occupant changed since launch (finished and possibly
        re-admitted under the async driver's one-step lag) are skipped -
        their speculative token is discarded."""
        self._count_event(s)
        detected = bool(int(s[0]))
        attributed = False
        for slot, req, pos_after in snap:
            if self.scheduler.active.get(slot) is not req:
                continue
            if detected and hit[slot]:
                self._attr(self.stats.record(req.id), s)
                attributed = True
            tok = nxt[slot, 0]
            reason = self._emit(req, tok, next_pos=pos_after)
            if reason is not None:
                self._finish(slot, reason)
            else:
                self._h_tokens[slot, 0] = tok
        if detected and not attributed:
            # evidence with no active-slot logit movement (e.g. a
            # fault on an inactive slot's row, or one the ladder
            # reverted exactly) stays in the tally but is not pinned
            # on any request
            self.stats.counters["faults_unattributed"] += 1
        if int(s[2]):
            self.stats.counters["residual_steps"] += 1

    def run(self) -> dict:
        """Drain the queue; returns the ServingStats report dict."""
        t0 = time.perf_counter()
        while self.step():
            pass
        self.stats.wall_s += time.perf_counter() - t0
        return self.stats.report()


# ---------------------------------------------------------------------------
# the parity oracle
# ---------------------------------------------------------------------------

def greedy_reference(params, cfg, prompt, max_new_tokens: int,
                     max_len: int, eos_id: Optional[int] = None) -> List:
    """Unbatched, unprotected greedy continuation (the clean-traffic
    parity oracle): batch-1 prefill at the exact prompt length + scalar-
    position decode, mirroring the session's emit/stop rules. Run it with
    a cfg whose abft=False to compare against protected serving."""
    toks = jnp.asarray(np.asarray(prompt))[None]
    plen = int(toks.shape[1])
    logits, _, caches = M.prefill(params, toks, cfg, max_len)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    if cfg.num_codebooks and nxt.ndim == 2:
        nxt = jnp.repeat(nxt[..., None], cfg.num_codebooks, -1)

    def host(t):
        t = np.asarray(t)[0, 0]
        return int(t) if np.ndim(t) == 0 else t.tolist()

    out = [host(nxt)]
    pos = plen
    while True:
        if (eos_id is not None and np.ndim(out[-1]) == 0
                and out[-1] == eos_id):
            break
        if len(out) >= max_new_tokens or pos >= max_len:
            break
        logits, _, caches = M.decode_step(
            params, nxt, caches, jnp.asarray(pos, jnp.int32), cfg)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        if cfg.num_codebooks and nxt.ndim == 2:
            nxt = jnp.repeat(nxt[..., None], cfg.num_codebooks, -1)
        out.append(host(nxt))
        pos += 1
    return out
