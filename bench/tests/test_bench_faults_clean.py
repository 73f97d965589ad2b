"""A run of a clean cell, past the look for a chip, at a small size:
sound, it is correct; with the timed path broken underneath, or with
the control in the program's place, `correct` comes out false."""
import jax.numpy as jnp
import pytest

from bench import cell, reference
from bench.tests.helpers import drive, small

CFG, TRAFFIC = small("resnet18", "b32-clean")


def stale():
    """A step that returns the previous request's answer, as if its state
    never moved."""
    last = []

    def breaker(args, logits, verdicts):
        last.append((logits, verdicts))
        return last[-2] if len(last) > 1 else last[-1]
    return breaker


def half_batch(args, logits, verdicts):
    """Half of the batch left out: no answer for its second half."""
    return logits.at[logits.shape[0] // 2:].set(0.0), verdicts


def altered(args, logits, verdicts):
    """One answer altered where it is produced: the first image's
    largest logit negated."""
    j = jnp.argmax(jnp.abs(logits[0]))
    return logits.at[0, j].multiply(-1.0), verdicts


def control():
    """The control in the program's place: the reference at the
    configuration's control precision (int8 operands)."""
    ref = reference.make(CFG["layers"], CFG["control_operand_dtype"])

    def breaker(args, logits, verdicts):
        return ref(args[0], args[1]), jnp.zeros_like(verdicts)
    return breaker


def test_sound_run_is_correct():
    rec, result = drive(CFG, TRAFFIC, 2 ** 31 + 5)
    assert cell.is_correct(result["numbers"]), result
    assert result["bad_images"] == 0 and rec.images_done > 0
    assert rec.compiled_window == 0


@pytest.mark.parametrize("breaker", [stale, lambda: half_batch,
                                     lambda: altered, control],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered", "control_int8"])
def test_broken_run_is_not_correct(breaker):
    _, result = drive(CFG, TRAFFIC, 11, breaker=breaker())
    assert not cell.is_correct(result["numbers"]), result
    assert result["numbers"]["logit_gap"] > cell.LIMITS["logit_gap"]
    assert result["bad_images"] > 0
