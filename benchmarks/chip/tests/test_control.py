"""The control reads as not correct; the program, on the same seeds, as correct.

The control is the plain reference put in the program's place one precision
step lower (``reference.py``: Gram products at ``Precision.HIGH``;
``round_reference.py``: parameters and momentum in bfloat16).  That
precision exists only on a TPU (the round's, whose program runs at the TPU's
default precision, is judged there too), so this test runs only where JAX
finds one:

    python3 -m pytest benchmarks/chip/tests/test_control.py

At the cells' own size the readings come from ``readings.py``.
"""
import pytest

import readings

CONFIG = {
    "boot.femnist-eq3": {"n_clients": 1024},
    "churn.femnist-eq3": {"n_clients": 1024},
    "round.mix4-lenet5": {},
}


@pytest.fixture(scope="module")
def tpu():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control's lower precision exists only on a TPU")


@pytest.mark.parametrize("workload", sorted(CONFIG))
def test_control_fails_program_passes(tpu, workload):
    recs = list(readings.readings(
        workload, [101], [101, 102, 103], 1.0,
        config_overrides=CONFIG[workload],
    ))
    program = [r for r in recs if r["mode"] == "program"]
    control = [r for r in recs if r["mode"] == "control"]
    assert all(r["correct"] for r in program), program
    assert not any(r["correct"] for r in control), control
