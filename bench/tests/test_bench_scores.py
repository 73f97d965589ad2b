"""bench/scores.py at a small size (CPU): the detect pass's score stays
far below 1 at every site on clean requests and lies above 1 at the
site each injected fault hits."""
from bench import scores
from bench.tests.helpers import small


def test_clean_below_one_injected_above():
    cfg, tr = small("resnet18", "b32-faults")
    out = scores.scores(cfg, tr, [2 ** 31 + 6], log=lambda msg: None)
    assert len(out["clean_max"]) == 18
    assert 0 < out["clean_max_all"] < 1
    assert set(out["faulted_min"]) == {"conv0", "conv8", "conv16"}
    assert min(out["faulted_min"].values()) > 1
