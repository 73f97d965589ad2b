"""Batched serving entry point - a thin shim over repro.serving.

The fixed-batch prefill+decode loop this module used to implement lives
in `repro.serving` now: `serve()` drives the async `ServingDriver`
(bounded admission + controller/runner split, the deployment shape) and
keeps the legacy surface (tokens array + summary stats) for the drivers
and tests, plus the full per-request report under "report". Pass
``driver=False`` to route through the synchronous `ProtectedSession`
instead (the single-stream building block - handy when bisecting a
driver-vs-session behavior difference).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m-smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
import repro.core as ft
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.serving import ProtectedSession, ServingDriver


def serving_inputs(cfg, batch: int, prompt_len: int, seed: int = 0):
    """(params, prompts) of a serve() run, made from `seed`: one key
    stream for the params, one for the prompts (a shared key would
    correlate the weights with the traffic)."""
    kp, kt = jax.random.split(jax.random.PRNGKey(seed))
    params = init_params(kp, cfg)
    tok_shape = ((batch, prompt_len, cfg.num_codebooks) if cfg.num_codebooks
                 else (batch, prompt_len))
    prompts = np.asarray(jax.random.randint(kt, tok_shape, 0,
                                            cfg.vocab_size, jnp.int32))
    return params, prompts


def serve(arch: str, batch: int, prompt_len: int, gen: int, seed: int = 0,
          audit_every: int = 0, driver: bool = True):
    cfg = C.get(arch)
    params, prompts = serving_inputs(cfg, batch, prompt_len, seed)
    max_len = prompt_len + gen

    plan = (ft.build_plan(params, cfg, batch=batch, seq=max_len)
            if cfg.abft else None)
    t0 = time.time()
    if driver:
        d = ServingDriver(params, cfg, plan, slots=batch, max_len=max_len,
                          audit_every=audit_every,
                          queue_capacity=max(batch * 4, 8))
        try:
            rids = [d.submit(prompts[i], max_new_tokens=gen).rid
                    for i in range(batch)]
            report = d.drain()
            tokens = {r: d.tokens_for(r) for r in rids}
        finally:
            d.close()
    else:
        sess = ProtectedSession(params, cfg, plan, slots=batch,
                                max_len=max_len, audit_every=audit_every)
        rids = [sess.submit(prompts[i], max_new_tokens=gen)
                for i in range(batch)]
        report = sess.run()
        tokens = {r: sess.tokens_for(r) for r in rids}
    wall = time.time() - t0

    tokens_out = np.stack([np.asarray(tokens[r], np.int32) for r in rids])
    recs = {r["id"]: r for r in report["requests"]}
    # prefill time = admission->first-token spans; decode is the rest of
    # the wall (the session accumulates stats on device - no per-step
    # report transfers to subtract out)
    t_prefill = sum(recs[r]["ttft_s"] or 0.0 for r in rids)
    t_decode = max(wall - t_prefill, 0.0)
    prefill_detected = sum(recs[r]["prefill_detected"] for r in rids)
    return tokens_out, {
        "prefill_s": t_prefill, "decode_s": t_decode,
        # every emitted token counts, including each prefill's argmax
        "tok_per_s": batch * gen / max(wall, 1e-9),
        "prefill_detected": prefill_detected,
        "faults_detected": report["counters"]["faults_detected"],
        "report": report,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--sync", action="store_true",
                    help="use the synchronous ProtectedSession loop")
    args = ap.parse_args()
    enable_compile_cache()
    toks, stats = serve(args.arch, args.batch, args.prompt_len, args.gen,
                        driver=not args.sync)
    rep = stats["report"]
    print(f"generated {toks.shape} tokens; "
          f"tok/s={stats['tok_per_s']:.1f} "
          f"ttft_p50={rep['ttft_p50_s']:.3f}s "
          f"faults={stats['faults_detected']}")


if __name__ == "__main__":
    main()
