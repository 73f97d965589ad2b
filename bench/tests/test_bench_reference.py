"""The plain reference and the harness's refusals, on the CPU."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np

from bench import cell, reference
from bench.tests.helpers import BENCH, ROOT, small


def test_reference_matches_the_system():
    """At float32 operands (the CPU's op), the system's protected forward
    and the plain reference agree to float32 rounding."""
    from repro.models import cnn
    cfg, _ = small("resnet18", "b32-clean")
    pcfg = cell.program_config(cfg)
    params = cell.init_params(jax.random.PRNGKey(1), cfg)
    x = cell.init_inputs(jax.random.PRNGKey(2), cfg, 3, 1)[0]
    want = np.asarray(reference.make(cfg["layers"], "float32")(params, x))
    got, _ = jax.jit(lambda p, xx: cnn.forward_cnn(p, xx, pcfg))(params, x)
    assert cell.logit_gap(got, want) < 1e-5
    bf16 = reference.make(cfg["layers"], "bfloat16")(params, x)
    assert cell.logit_gap(bf16, want) > 1e-4


def test_seeds_above_32_bits_stay_distinct():
    a, b = cell.key_from_seed(3), cell.key_from_seed(2 ** 32 + 3)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "resnet18-b32-clean", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert '"metrics"' not in p.stdout


def test_refuses_an_unknown_device_kind():
    from bench import run

    @dataclasses.dataclass
    class Dev:
        platform: str = "tpu"
        device_kind: str = "TPU v9 imaginary"

    class FakeJax:
        @staticmethod
        def devices():
            return [Dev()]

    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    info, err = run.device_info(FakeJax, 1, peaks)
    assert info is None and "peaks.json" in err
    info, err = run.device_info(FakeJax, 4, peaks)
    assert info is None and "4 chips" in err
