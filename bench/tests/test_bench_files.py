"""BENCHMARK.json resolves by name to the files that hold each piece, and
the operation counts of bench/flops.py match the networks' sizes."""
import os
import re

import pytest
from bench import flops
from bench.tests.helpers import BENCH, ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("config,gflops", [("resnet18", 3.590)])
def test_flops_per_image(config, gflops):
    cfg = load(BENCH, "configs", f"{config}.json")
    assert round(flops.flops_per_image(cfg) / 1e9, 3) == gflops


def test_site_bytes_and_bound():
    cfg = load(BENCH, "configs", "resnet18.json")
    conv0 = flops.sites(cfg, 2)[0]
    # bf16 input and weights, f32 output of 2 x 64 x 112 x 112
    assert conv0["out_hw"] == 112
    assert conv0["bytes"] == (2 * (2 * 3 * 224 * 224 + 64 * 3 * 49)
                              + 4 * 2 * 64 * 112 * 112)
    peaks = {"flops_per_s": 1.0, "bytes_per_s": 1e30}
    assert flops.site_min_seconds(conv0, peaks) == conv0["flops"]


def test_every_name_resolves(bench_doc):
    for c in bench_doc["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert load(ROOT, c["file"])["name"] == c["name"]
    configs = {c["name"] for c in bench_doc["configs"]}
    for w in bench_doc["workloads"]:
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
    for m in bench_doc["end_to_end"] + bench_doc["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))


def test_names_and_units(bench_doc):
    names = ([c["name"] for c in bench_doc["configs"]]
             + [w["name"] for w in bench_doc["workloads"]]
             + [w["traffic"] for w in bench_doc["workloads"]]
             + [m["name"] for m in bench_doc["end_to_end"]
                + bench_doc["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    for m in bench_doc["end_to_end"] + bench_doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    cells = {w["name"] for w in bench_doc["workloads"]}
    e2e = {m["name"] for m in bench_doc["end_to_end"]}
    for m in bench_doc["end_to_end"] + bench_doc["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench_doc["per_layer"]:
        assert m["moves"] in e2e


def test_config_layers_are_the_systems(bench_doc):
    from bench import cell
    for c in bench_doc["configs"]:
        cell.program_config(load(ROOT, c["file"]))


def test_peaks_table():
    peaks = load(BENCH, "peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
