"""The phase scopes of the protected sites (bench/phases.py): every op
the timed step lowers to under a site lies in exactly one phase (CPU),
and the readers of the phases on a recorded trace of the fault cell
(bench/tests/phase_fixture.json)."""
import os
import re

import pytest

from bench import cell, phases
from bench.run import load_reader
from bench.tests.helpers import load, small

PHASE_PARTS = set(phases.PHASES) - {"correct"}


def _op_names(traffic):
    cfg, tr = small("resnet18", traffic)
    pcfg = cell.program_config(cfg)
    prep = cell.prepare(cfg, tr, 5, pcfg)
    sites = list(prep.plan.entries)
    step = cell.make_step(pcfg, prep.plan, cfg["correction"], sites)
    hlo = step.lower(*prep.requests[-1]).as_text(dialect="hlo",
                                                   debug_info=True)
    return re.findall(r'op_name="([^"]*)"', hlo), sites


def test_every_site_op_lies_in_one_phase():
    """Outside a correction, an op under a site names one phase (an op
    made from several source ops may repeat it); under `correct` the
    rerun's own sites nest their phases and all of it is correction."""
    names, sites = _op_names("b32-faults")
    seen = set()
    for name in names:
        parts = [c for path in name.split(";") for c in path.split("/")]
        in_site = any(c in sites for c in parts)
        if "correct" in parts:
            seen.add("correct")
            continue
        got = {c for c in parts if c in PHASE_PARTS}
        if in_site:
            assert len(got) == 1, name
            seen |= got
        else:
            assert not got, name
    assert seen == set(phases.PHASES)


def test_phase_of():
    sites = ("conv0", "fc")
    p = phases.phase_of
    assert p("jit(bench_step)/conv0/detect/checksum_conv/gather:",
             sites) == "detect"
    assert p("jit(bench_step)/cond/branch_1_fun/correct/conv0/encode/dot:",
             sites) == "correct"
    assert p("jit(bench_step)/fc/op/dot_general:", sites) == "op"
    assert p("jit(bench_step)/conv0/reshape:", sites) == "unphased"
    assert p("jit(bench_step)/reduce_window_max:", sites) == "-"
    assert p("", sites) == "-"
    # whole components only: a site or op named like a phase is not one
    assert p("jit(bench_step)/conv0/operand:", sites) == "unphased"
    assert p("a/fc/reshape;a/fc/detect/squeeze", sites) == "detect"


@pytest.fixture
def fixture():
    return load(os.path.dirname(__file__), "phase_fixture.json")


def _ctx(fx):
    class Done:
        def __init__(self, flagged):
            import numpy as np
            self.verdicts = np.zeros((18, 3), np.int32)
            self.verdicts[8, 0] = flagged

    class Run:
        done = [Done(f) for f in fx["flagged"]]

    tr = {"ops": fx["ops"], "busy_s": fx["busy_s"],
          "window_s": fx["window_s"], "steps_s": fx["steps_s"]}
    sites = [{"name": f"conv{i}"} for i in range(17)] + [{"name": "fc"}]
    return {"trace": tr, "run": Run, "sites": sites}


def _sum(ops, pred):
    return sum(o[0] for o in ops if pred(o[1]))


def test_phase_readers(fixture, capsys):
    ctx = _ctx(fixture)
    ops, busy = fixture["ops"], fixture["busy_s"]
    detect = _sum(ops, lambda s: "/detect/" in s and "correct" not in s)
    encode = _sum(ops, lambda s: "/encode/" in s and "correct" not in s)
    correct = _sum(ops, lambda s: "/correct/" in s)
    assert detect and encode and correct
    assert load_reader("detect_share")(ctx) == pytest.approx(
        100 * detect / busy)
    assert load_reader("encode_share")(ctx) == pytest.approx(
        100 * encode / busy)
    n = sum(fixture["flagged"])
    assert n == 1
    assert load_reader("rerun_ms")(ctx) == pytest.approx(1e3 * correct / n)
    err = capsys.readouterr().err
    assert "phase table: conv0 " in err and "reruns: 1 of 2" in err


def test_detect_holds_the_checksum_convs(fixture):
    """Outside a correction every op under `checksum_conv` is detection,
    so on clean steps detect_share is at least checksum_share (which
    also counts the rerun's checksum convs where a step reruns)."""
    ctx = _ctx(fixture)
    clean = [o for o in fixture["ops"] if "correct" not in o[1]]
    conv = [o for o in clean if "checksum_conv" in o[1]]
    assert conv
    assert all(phases.phase_of(o[1], ()) == "detect" for o in conv)
    ctx["trace"]["ops"] = clean
    assert (load_reader("detect_share")(ctx)
            >= load_reader("checksum_share")(ctx))


def test_readers_read_nothing_without_phases(fixture):
    """A program without the phase scopes (the scopes stripped from the
    fixture) leaves the three metrics out instead of reading zero."""
    ctx = _ctx(fixture)
    strip = re.compile(r"/(op|encode|detect|correct|inject)(?=/)")
    ctx["trace"]["ops"] = [[o[0], strip.sub("", o[1])] + o[2:]
                           for o in fixture["ops"]]
    for name in ("detect_share", "encode_share", "rerun_ms"):
        assert load_reader(name)(ctx) is None, name
    ctx["run"].done = []
    assert load_reader("rerun_ms")(ctx) is None


@pytest.mark.parametrize("name", ["detect_share", "encode_share",
                                  "rerun_ms"])
def test_reader_says_why_it_reads_nothing(fixture, capsys, name):
    """A trace with no phase scope (here stripped; on the chip also an
    executable another checkout left in a shared compile cache) gives
    no reading and a reason on standard error, which a program with
    the scopes never prints."""
    ctx = _ctx(fixture)
    reason = f"{name}: no op in the trace has a phase scope"
    assert load_reader(name)(ctx) is not None
    assert reason not in capsys.readouterr().err
    strip = re.compile(r"/(op|encode|detect|correct|inject)(?=/)")
    ctx["trace"]["ops"] = [[o[0], strip.sub("", o[1])] + o[2:]
                           for o in fixture["ops"]]
    assert load_reader(name)(ctx) is None
    assert reason in capsys.readouterr().err
