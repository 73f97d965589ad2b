"""The measured roofline cost model: per-host calibration caching,
deterministic classification given a cached calibration, and the derived
plan-build decisions (chunk sizing, profile pruning window)."""
import json
import math

import pytest

import repro.core as core
from repro.core.cost_model import (CACHE_SCHEMA, HostPeaks,
                                   MeasuredCostModel, cost_model_doc,
                                   measure_peaks, shape_bytes, shape_flops)
from repro.core.policy import CostModel, OpShape


def _write_cache(path, peak_flops=2e11, hbm_bw=2e10):
    import jax
    path.write_text(json.dumps({
        "schema": CACHE_SCHEMA, "backend": jax.default_backend(),
        "host": "testhost", "peak_flops": peak_flops, "hbm_bw": hbm_bw}))
    return str(path)


# --------------------------------------------------------------------------
# calibration cache
# --------------------------------------------------------------------------

def test_measure_peaks_writes_then_loads_cache(tmp_path):
    """First call measures and writes; the second call must load the same
    numbers from the cache (source='cache') - plan builds are
    deterministic given the calibration file."""
    path = str(tmp_path / "roofline.json")
    p1 = measure_peaks(cache_path=path)
    if p1.source != "measured":
        pytest.skip("microbench could not run on this backend")
    p2 = measure_peaks(cache_path=path)
    assert p2.source == "cache"
    assert p2.peak_flops == p1.peak_flops and p2.hbm_bw == p1.hbm_bw
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == CACHE_SCHEMA
    assert doc["peak_flops"] == p1.peak_flops


def test_measure_peaks_stale_backend_cache_rejected(tmp_path):
    """A cache recorded under another backend is stale: it must be
    re-measured, not trusted."""
    path = tmp_path / "roofline.json"
    path.write_text(json.dumps({
        "schema": CACHE_SCHEMA, "backend": "not-a-backend",
        "host": "x", "peak_flops": 1.0, "hbm_bw": 1.0}))
    p = measure_peaks(cache_path=str(path))
    assert p.source == "measured"
    assert p.peak_flops != 1.0


def test_measure_peaks_raises_when_a_bench_cannot_run(tmp_path,
                                                     monkeypatch):
    """No made-up peaks: a microbench that fails fails the calibration
    (and writes no cache) instead of degrading to fallback numbers."""
    from repro.core import cost_model as CM

    def broken():
        raise RuntimeError("no device")

    monkeypatch.setattr(CM, "_bench_gemm_flops", broken)
    path = tmp_path / "roofline.json"
    with pytest.raises(RuntimeError, match="no device"):
        measure_peaks(cache_path=str(path), refresh=True)
    assert not path.exists()


def test_measure_peaks_refresh_overwrites(tmp_path):
    path = _write_cache(tmp_path / "roofline.json",
                        peak_flops=1.0, hbm_bw=1.0)
    p = measure_peaks(cache_path=path, refresh=True)
    assert p.peak_flops != 1.0


def test_host_peaks_ridge():
    p = HostPeaks(2e11, 2e10, "cpu", "h", "measured")
    assert p.ridge == pytest.approx(10.0)
    assert p.doc()["ridge"] == pytest.approx(10.0)


# --------------------------------------------------------------------------
# deterministic classification
# --------------------------------------------------------------------------

def test_classify_deterministic_given_cached_calibration(tmp_path):
    """Two models built from the same cache file classify every shape
    identically - the reproducibility contract plan builds rely on."""
    path = _write_cache(tmp_path / "roofline.json")
    m1 = MeasuredCostModel.from_host(cache_path=path)
    m2 = MeasuredCostModel.from_host(cache_path=path)
    assert m1.source == "cache" == m2.source
    shapes = [OpShape(n=8, m=256, ch=96, r=5, h=27),
              OpShape(n=16, m=4096, ch=1024),
              OpShape(n=2, m=64, ch=64, r=3, h=8)]
    for s in shapes:
        assert m1.classify(s) == m2.classify(s)
        assert m1.detect_chunk(512) == m2.detect_chunk(512)
        assert m1.should_profile(s) == m2.should_profile(s)


def test_classify_bound_tracks_ridge():
    """intensity >= ridge <=> compute-bound; the same shape flips verdict
    when the host's ridge moves across its intensity."""
    s = OpShape(n=8, m=256, ch=96, r=5, h=27)
    inten = shape_flops(s) / shape_bytes(s)
    low_ridge = MeasuredCostModel(peak_flops=inten * 0.5 * 1e9,
                                  hbm_bw=1e9)
    high_ridge = MeasuredCostModel(peak_flops=inten * 2.0 * 1e9,
                                   hbm_bw=1e9)
    c_lo, c_hi = low_ridge.classify(s), high_ridge.classify(s)
    assert c_lo["bound"] == "compute" and c_hi["bound"] == "bandwidth"
    assert c_lo["intensity"] == pytest.approx(inten)
    # predicted tiers are ordered: every scheme adds cost over base, and
    # the full ladder tiers dominate detection-only
    for c in (c_lo, c_hi):
        p = c["predicted_us"]
        assert p["base"] < p["coc"] <= min(p["rc"], p["clc"], p["fc"])


def test_measured_alpha_beta_are_real_seconds():
    m = MeasuredCostModel(peak_flops=2e11, hbm_bw=2e10)
    assert m.alpha == pytest.approx(2.0 / 2e11)
    assert m.beta == pytest.approx(4.0 / 2e10)
    # pricing flows into the shared Table-4 terms (inherited CostModel)
    s = OpShape(n=8, m=64, ch=32)
    assert m.t_coc(s) > 0 and m.t_rc(s) > 0


# --------------------------------------------------------------------------
# derived plan-build decisions
# --------------------------------------------------------------------------

def test_detect_chunk_power_of_two_and_clamped():
    m = MeasuredCostModel(peak_flops=2e11, hbm_bw=2e10)
    c = m.detect_chunk(512)
    assert c & (c - 1) == 0 and 256 <= c <= 4096
    # slow host -> small chunks, floor-clamped
    slow = MeasuredCostModel(peak_flops=1e6, hbm_bw=1e6)
    assert slow.detect_chunk(512) == 256
    # monstrous bandwidth -> ceiling-clamped
    fast = MeasuredCostModel(peak_flops=1e15, hbm_bw=1e15)
    assert fast.detect_chunk(512) == 4096


def test_should_profile_window():
    s = OpShape(n=8, m=256, ch=96, r=5, h=27)
    inten = shape_flops(s) / shape_bytes(s)
    # ridge == intensity: ratio 1.0, inside any sane window
    at_ridge = MeasuredCostModel(peak_flops=inten * 1e9, hbm_bw=1e9)
    assert at_ridge.should_profile(s)
    # ridge 100x the intensity: ratio 0.01, far outside
    far = MeasuredCostModel(peak_flops=inten * 100 * 1e9, hbm_bw=1e9)
    assert not far.should_profile(s)


def test_cost_model_doc_names_the_class():
    doc = cost_model_doc(MeasuredCostModel(peak_flops=2e11, hbm_bw=2e10))
    assert doc["class"] == "MeasuredCostModel"
    assert doc["params"]["ridge"] == pytest.approx(10.0)
    legacy = cost_model_doc(CostModel())
    assert legacy["class"] == "CostModel"
    assert legacy["params"] == {"alpha": legacy["alpha"],
                                "beta": legacy["beta"]}
    assert math.isfinite(doc["alpha"]) and doc["alpha"] > 0


def test_core_exports():
    assert core.MeasuredCostModel is MeasuredCostModel
    assert core.measure_peaks is measure_peaks
