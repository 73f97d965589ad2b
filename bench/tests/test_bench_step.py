"""The timed step lowers to one program for every seed (CPU)."""
import hashlib

from bench import cell
from bench.tests.helpers import small


def _step_text(cfg, traffic, seed):
    pcfg = cell.program_config(cfg)
    prep = cell.prepare(cfg, traffic, seed, pcfg)
    step = cell.make_step(pcfg, prep.plan, cfg["correction"],
                          list(prep.plan.entries))
    text = step.lower(*prep.requests[-1]).as_text()
    return hashlib.md5(text.encode()).hexdigest()


def test_two_seeds_lower_to_one_program():
    """The compile cache hit that a warm run's set-up rests on: a new seed
    gives new weights and checksums, never a new program."""
    for traffic in ("b32-clean", "b32-faults"):
        cfg, tr = small("resnet18", traffic)
        assert (_step_text(cfg, tr, 3) ==
                _step_text(cfg, tr, 2 ** 31 + 3)), traffic
