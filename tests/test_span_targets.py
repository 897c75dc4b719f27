"""The program calls the chip benchmark times from outside stay plain functions.

A per-layer metric reader under ``benchmarks/chip/metrics/`` may declare
``SPANS = {name: "package.module:attr"}``: in a traced run the benchmark
wraps each target in a host span and reads its time.  A refactor that
renames such a target, or turns it into a property, static method or
class method, would leave the metric with nothing to read.  This resolves
every declared target the way the benchmark does and checks that it is a
plain function.
"""
import ast
import importlib
import inspect
from pathlib import Path

import pytest

METRICS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "metrics"


def _declared() -> dict[str, str]:
    targets = {}
    for path in sorted(METRICS.glob("*.py")):
        for node in ast.parse(path.read_text()).body:
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "SPANS" for t in node.targets
            ):
                targets.update(ast.literal_eval(node.value))
    return targets


DECLARED = _declared()


def test_the_harness_wrappers_are_declared():
    assert {"hc", "replay", "commit"} <= set(DECLARED)


@pytest.mark.parametrize("name", sorted(DECLARED))
def test_span_target_is_a_plain_function(name):
    mod_name, _, path = DECLARED[name].partition(":")
    owner = importlib.import_module(mod_name)
    *parents, attr = path.split(".")
    for part in parents:
        owner = getattr(owner, part)
    target = inspect.getattr_static(owner, attr)
    assert inspect.isfunction(target), f"{DECLARED[name]} is {type(target).__name__}"
