"""trace_reduce and the trace-based metric readers on a recorded fixture:
two steps of resnet18-b32-clean on a TPU v5 lite, their host spans and
12 of their device ops (bench/tests/trace_fixture.json)."""
import os

import pytest

from bench import flops, trace_reduce
from bench.run import load_reader
from bench.tests.helpers import BENCH, load

NS = 1e-9
# the fixture's 12 op durations, in ns; none overlaps another
DURS = [339337.5, 72521.094, 159851.25, 772.422, 22050.0, 112246.25,
        339470.156, 72076.25, 159717.578, 772.5, 22049.922, 112090.078]
# window: the first step starts on the device before its dispatch span
# on the host (the clocks agree to a millisecond); it ends with the
# last fetch span, after the last step
LO, HI = 49952631.25, 67781969.0 + 13311999.0


@pytest.fixture
def summary():
    ev = load(os.path.dirname(__file__), "trace_fixture.json")
    return trace_reduce.summarize(ev)


def test_window_busy_and_gaps(summary):
    assert summary["window_s"] == pytest.approx((HI - LO) * NS, rel=1e-12)
    assert summary["busy_s"] == pytest.approx(sum(DURS) * NS, rel=1e-12)
    gaps = summary["idle_gaps"]
    # the longest gap: from the end of the last op to the end of the
    # window, inside the second fetch
    assert gaps[0][0] == "fetch"
    assert gaps[0][1] == pytest.approx(
        (HI - (71642358.75 + 112090.078)) * NS, rel=1e-9)
    assert all(b <= a for (_, a), (_, b) in zip(gaps, gaps[1:]))


def test_steps_and_scopes(summary):
    assert summary["steps_s"] == pytest.approx(
        [14160006.25 * NS, 14158974.922 * NS])
    by = dict(summary["device_ops"])
    assert by["conv0/conv_general_dilated: [convolution fusion]"] == \
        pytest.approx((339337.5 + 339470.156) * NS)
    assert by["conv0/checksum_conv/gather: [custom fusion]"] == \
        pytest.approx((112246.25 + 112090.078) * NS)
    assert by["- [copy-done]"] == pytest.approx((72521.094 + 72076.25) * NS)


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 7)]


def test_site_of():
    sites = {"conv3", "fc"}
    assert trace_reduce.site_of("jit(bench_step)/conv3/dot_general:",
                                sites) == "conv3"
    assert trace_reduce.site_of("jit(bench_step)/reduce_window_max:",
                                sites) is None


def test_readers(summary):
    cfg = load(BENCH, "configs", "resnet18.json")
    peaks = load(BENCH, "peaks.json")["TPU v5 lite"]
    sites = [s for s in flops.sites(cfg, 32)
             if s["name"] in ("conv0", "conv3", "fc")]

    class Run:
        images_done = 64

    ctx = {"trace": summary, "run": Run, "peaks": peaks, "sites": sites,
           "flops_per_image": flops.flops_per_image(cfg)}
    busy, window = sum(DURS), HI - LO
    assert load_reader("device_idle_share")(ctx) == pytest.approx(
        100 * (1 - busy / window))
    assert load_reader("checksum_share")(ctx) == pytest.approx(
        100 * (112246.25 + 112090.078) / busy)
    # the sites' own ops: the two conv_general_dilated of conv0 and conv3
    # and fc's GEMM in each step; conv3's checksum dot_general does ~2 %
    # of conv3's flops and is left out
    spent = (339337.5 + 159851.25 + 772.422
             + 339470.156 + 159717.578 + 772.5) * NS
    least = sum(flops.site_min_seconds(s, peaks) for s in sites)
    assert load_reader("op_roofline")(ctx) == pytest.approx(
        100 * 2 * least / spent)
    assert load_reader("mfu")(ctx) == pytest.approx(
        100 * 64 * flops.flops_per_image(cfg)
        / (window * NS * peaks["flops_per_s"]))


def _roofline_ctx(summary, names):
    cfg = load(BENCH, "configs", "resnet18.json")
    peaks = load(BENCH, "peaks.json")["TPU v5 lite"]
    sites = [s for s in flops.sites(cfg, 32) if s["name"] in names]
    return {"trace": summary, "peaks": peaks, "sites": sites}, sites, peaks


def test_op_roofline_site_without_its_op(summary, capsys):
    """A site whose own op the trace lacks (conv5 here) leaves the
    metric out, rather than adding its least time to the numerator
    alone."""
    ctx, _, _ = _roofline_ctx(summary, ("conv0", "conv3", "conv5", "fc"))
    assert load_reader("op_roofline")(ctx) is None
    assert "conv5" in capsys.readouterr().err


def test_op_roofline_counts_a_sites_custom_call(summary):
    """A site pinned to a Pallas kernel: its custom call, with no flops
    and no convolution category, is the site's own op."""
    pallas = [0.0004, "jit(bench_step)/conv5/pallas_call:", "custom-call",
              0.0, "tpu_custom_call.7"]
    tr = dict(summary, ops=summary["ops"] + [pallas])
    ctx, sites, peaks = _roofline_ctx(tr, ("conv0", "conv3", "conv5",
                                           "fc"))
    spent = (339337.5 + 159851.25 + 772.422
             + 339470.156 + 159717.578 + 772.5) * NS + 0.0004
    least = sum(flops.site_min_seconds(s, peaks) for s in sites)
    assert load_reader("op_roofline")(ctx) == pytest.approx(
        100 * 2 * least / spent)


def test_correction_ms():
    class Done:
        def __init__(self, req):
            self.req = req

    class Run:
        # requests 0 and 1 are clean, request 2 injects a fault at conv8
        meta = [(0, -1), (1, -1), (0, 8)]
        done = [Done(r) for r in (0, 1, 2, 0)]

    ctx = {"trace": {"steps_s": [0.010, 0.012, 0.031, 0.011]}, "run": Run}
    assert load_reader("correction_ms")(ctx) == pytest.approx(20.0)
    ctx["trace"]["steps_s"] = ctx["trace"]["steps_s"][:3]
    assert load_reader("correction_ms")(ctx) is None


def test_extract_reads_host_spans(tmp_path):
    """A profiler trace written here (no device plane on the CPU) yields
    the harness's host spans through the proto reader."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: a * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in ("dispatch", "fetch", "check"):
        with jax.profiler.TraceAnnotation(name):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace_reduce.extract(trace_reduce.find_xplane(str(tmp_path)))
    assert sorted(s[2] for s in ev["spans"]) == ["check", "dispatch",
                                                 "fetch"]
    assert all(s[1] > 0 for s in ev["spans"])
