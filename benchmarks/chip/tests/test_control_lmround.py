"""The LM round's control, and each fault of ``test_faults_lmround.py``,
read as not correct at the cell's own size; the program, on the same seed,
as correct.

The control is the reference's round (``lfm2_reference.py``) put in the
program's place one precision step below the configuration's float32:
parameters held in bfloat16 between local steps.  The program's products
run at the TPU's default precision, so the comparison is the chip's; this
file runs only where JAX finds a TPU, at the cell's own size, and prints
each run's readings as a JSON line:

    python3 -m pytest -s benchmarks/chip/tests/test_control_lmround.py
"""
import json

import pytest

import readings
from test_faults_lmround import FAULTS

CELL = "lmround.lfm2-8b-a1b-fed"
SEEDS = [101, 102, 103]


@pytest.fixture(scope="module")
def tpu():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control's lower precision is judged on a TPU")


def _readings(*args, **kw) -> list:
    recs = list(readings.readings(CELL, *args, 1.0, **kw))
    for r in recs:
        print(json.dumps(r), flush=True)
    return recs


def test_control_fails_program_passes(tpu):
    recs = _readings(SEEDS[:1], SEEDS)
    program = [r for r in recs if r["mode"] == "program"]
    control = [r for r in recs if r["mode"] == "control"]
    assert all(r["correct"] for r in program), program
    assert not any(r["correct"] for r in control), control
    # through a gap: the reference drops no pair, and the logits and the
    # routing are the program's own on the initial model
    for r in control:
        over = {n for n, c in r["checks"].items() if c["value"] > c["limit"]}
        assert over & {"first_round_worst_gap", "first_round_median_gap"}, r
        assert r["checks"]["dropped_pairs"]["value"] == 0, r


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_fails_at_the_cells_size(tpu, monkeypatch, fault):
    plant, overrides, names = FAULTS[fault]
    plant(monkeypatch)
    for r in _readings(SEEDS, [], config_overrides=overrides):
        over = {n for n, c in r["checks"].items() if c["value"] > c["limit"]}
        assert not r["correct"] and over & set(names), (fault, r)
