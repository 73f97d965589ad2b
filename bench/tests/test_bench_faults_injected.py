"""A run of the fault cell, past the look for a chip, at a small size:
sound, every injected fault is detected, attributed, corrected and
checked against the reference; with the correction skipped underneath
(the report still claiming it), `correct` comes out false."""
import jax.numpy as jnp

from bench import cell
from bench.tests.helpers import drive, small

CFG, TRAFFIC = small("resnet18", "b32-faults")


def test_sound_run_is_correct():
    rec, result = drive(CFG, TRAFFIC, 2 ** 31 + 6)
    assert cell.is_correct(result["numbers"]), result
    faulted = [d for d in rec.done if rec.meta[d.req][1] >= 0]
    assert faulted and len(faulted) < len(rec.done)


def test_skipped_correction_is_not_correct(monkeypatch):
    from repro.core import workflow

    def no_rerun(any_flag, clean_out, correct_fn, n_layers, base_by=None,
                 base_resid=None):
        # the detect pass's output returned as if corrected
        by = jnp.where(any_flag, 1, 0) * jnp.ones((n_layers,), jnp.int32)
        return clean_out, by, jnp.zeros((n_layers,), jnp.int32)

    monkeypatch.setattr(workflow, "run_deferred", no_rerun)
    cfg = dict(CFG, name="resnet18-no-rerun")   # its own compiled step
    _, result = drive(cfg, TRAFFIC, 13)
    assert result["numbers"]["missed_faults"] == 0     # the report lies
    assert result["numbers"]["logit_gap"] > cell.LIMITS["logit_gap"]
    assert not cell.is_correct(result["numbers"])
