"""The flop and byte counters against hand counts at a tiny K."""
import itertools

import pytest

import flops

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def _brute_flops(K, n, p, measure):
    """One multiply and one add per feature, per Gram entry, per pair."""
    entries = [(r, r) for r in range(p)] if measure == "eq3" else list(itertools.product(range(p), repeat=2))
    return sum(2 * n * len(entries) for _ in itertools.combinations(range(K), 2))


@pytest.mark.parametrize("measure", ["eq3", "eq2"])
@pytest.mark.parametrize("K,n,p", [(2, 3, 1), (3, 4, 2), (5, 7, 3)])
def test_proximity_flops_match_brute_count(K, n, p, measure):
    assert flops.proximity_flops(K, n, p, measure) == _brute_flops(K, n, p, measure)


def test_hand_counts_k3():
    # K=3, n=4, p=2: 3 pairs; eq3 2 entries x 4 features x 2 flops; eq2 4 entries
    assert flops.proximity_flops(3, 4, 2, "eq3") == 48
    assert flops.proximity_flops(3, 4, 2, "eq2") == 96
    # 3*4*2 float32 signatures in, 3 float32 pairs out
    assert flops.proximity_bytes(3, 4, 2) == 4 * 24 + 4 * 3


def test_roofline_picks_the_larger_bound():
    assert flops.roofline_s(1000.0, 10.0, PEAK) == (10.0, "compute")
    assert flops.roofline_s(10.0, 1000.0, PEAK) == (100.0, "memory")


def test_femnist_eq3_is_compute_bound_on_v5e():
    import json
    from pathlib import Path

    peak = json.loads((Path(flops.__file__).parent / "peaks.json").read_text())["kinds"]["TPU v5 lite"]
    f = flops.proximity_flops(3550, 784, 5, "eq3")
    assert f == pytest.approx(4.94e10, rel=1e-3)
    t, bound = flops.roofline_s(f, flops.proximity_bytes(3550, 784, 5), peak)
    assert bound == "compute" and t == pytest.approx(f / 197e12)



def test_lenet5_hand_count():
    # 32x32x3 in, 40 classes: conv1 28*28*6 outputs of 5*5*3 MACs; pool to
    # 14x14; conv2 10*10*16 outputs of 5*5*6; pool to 5x5x16 = 400 features;
    # fc 400*120, 120*84, 84*40
    macs = 28 * 28 * 6 * 75 + 10 * 10 * 16 * 150 + 400 * 120 + 120 * 84 + 84 * 40
    assert macs == 654_240
    assert flops.lenet5_forward_macs(32, 3, 40) == macs
    assert flops.lenet5_train_flops(32, 3, 40) == 3 * 2 * macs
    # a MIX-4 round: 10 clients x 45 steps x 20 samples
    assert 9000 * flops.lenet5_train_flops(32, 3, 40) == pytest.approx(35.33e9, rel=1e-3)
