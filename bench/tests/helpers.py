"""Paths and small cell sizes shared by the benchmark's tests."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def small(config: str, traffic: str):
    """A cell's configuration and traffic at a size the CPU runs in
    seconds. Off a TPU the system's op multiplies float32 operands, so
    the reference does too."""
    cfg = load(BENCH, "configs", f"{config}.json")
    cfg.update(width_scale=0.12, img=32, operand_dtype="float32")
    tr = load(BENCH, "traffic", f"{traffic}.json")
    tr.update(batch=4)
    return cfg, tr


# the set-up and check sizes of bench/cell.py at the small size
SMALL_SIZES = {"POOL": 2, "WARMUP_SECONDS": 0.1, "REFERENCE_BLOCK": 4}


# compiled steps by (configuration, traffic), shared by every test and
# seed of this process
_STEPS = {}


def drive(cfg, traffic, seed, breaker=None, seconds=0.3):
    """Set-up, window and check of one run of the harness, past its look
    for a chip, with the timed step's outputs passed through
    `breaker(args, logits, verdicts)` when one is given. The compiled
    step is kept per configuration and traffic: every seed shares it."""
    import time

    from bench import cell
    real = cell.compile_step

    def compile_step(prep, pcfg, cfg_):
        key = (json.dumps(cfg, sort_keys=True),
               json.dumps(traffic, sort_keys=True))
        if key not in _STEPS:
            _STEPS[key] = real(prep, pcfg, cfg_)
        step = _STEPS[key]
        if breaker is None:
            return step
        return lambda *args: breaker(args, *step(*args))

    saved = {k: getattr(cell, k) for k in SMALL_SIZES}
    for k, v in SMALL_SIZES.items():
        setattr(cell, k, v)
    cell.compile_step = compile_step
    try:
        rec, result, _ = cell.run(cfg, traffic, seed, seconds,
                                  time.perf_counter(), cell.Counter(),
                                  log=lambda msg: None)
    finally:
        cell.compile_step = real
        for k, v in saved.items():
            setattr(cell, k, v)
    return rec, result
