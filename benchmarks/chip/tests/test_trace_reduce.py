"""The trace reduction on a hand-built trace and on a small chip trace."""
import dataclasses
import json
import lzma
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


def _profile(device_ops, host_spans, modules=(("jit_f(123)", 10, 50),)):
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev(n, s, d) for n, s, d in modules]),
        Line("XLA Ops", [Ev(n, s, d) for n, s, d in device_ops]),
    ])
    host = Plane("/host:CPU", [Line("python3", [Ev(n, s, d) for n, s, d in host_spans])])
    return Profile([host, dev])


HAND = _profile(
    device_ops=[
        ("%proximity.1 = f32[8,8] custom-call(...)", 10, 20),   # [10, 30)
        ("%fusion.2 = f32[8,8] fusion(...)", 25, 15),           # [25, 40), overlaps
        ("%copy = f32[8] copy(...)", 50, 10),                   # [50, 60)
        ("%proximity.1 = f32[8,8] custom-call(...)", 90, 20),   # [90, 110), clipped
    ],
    host_spans=[
        ("span.window", 0, 100),
        ("span.bootstrap", 0, 70),
        ("span.hc", 35, 30),                                     # inside bootstrap
        ("PjitFunction(f)", 1, 2),                               # not a span
    ],
)


def test_hand_built_trace():
    ts = trace_reduce.reduce_profile(HAND)
    assert ts.window_s == pytest.approx(100e-9)
    # busy = [10, 40) + [50, 60) + [90, 100)
    assert ts.busy_s == pytest.approx(50e-9)
    assert ts.idle_share == pytest.approx(0.5)
    assert ts.kernel_s["proximity"] == pytest.approx(30e-9)
    assert ts.kernel_calls["proximity"] == 2
    assert dict(ts.top_ops) == pytest.approx({
        "jit_f/proximity": 20e-9, "jit_f/fusion": 15e-9,
        "jit_f/copy": 10e-9, "?/proximity": 10e-9,
    })
    assert ts.module_s == pytest.approx({"jit_f": 50e-9})
    assert ts.module_calls == {"jit_f": 1}
    # gaps [0, 10) in bootstrap, [40, 50) in hc; [60, 90) split: [60, 65) in
    # hc, [65, 70) in bootstrap, [70, 90) in no span
    assert dict(ts.idle_by_host) == pytest.approx({
        "bootstrap": 15e-9, "hc": 15e-9, "no span": 20e-9,
    })


def test_gap_over_sibling_spans_is_split():
    """One idle gap that two sibling spans cover in turn goes to each by its
    share, not whole to the span open at its midpoint."""
    ts = trace_reduce.reduce_profile(_profile(
        device_ops=[("%copy = x", 0, 20), ("%copy = x", 45, 55)],   # busy [0, 20), [45, 100)
        host_spans=[
            ("span.window", 0, 100),
            ("span.round", 0, 30),
            ("span.merge_forest", 10, 5),                             # inside round, before the gap
            ("span.round", 30, 60),                                   # [30, 90)
            ("span.run_local", 30, 10),                               # inside the second round
        ],
    ))
    # the gap [20, 45): [20, 30) first round, [30, 40) run_local, [40, 45) second round
    assert dict(ts.idle_by_host) == pytest.approx({"round": 15e-9, "run_local": 10e-9})
    assert sum(v for _, v in ts.idle_by_host) == pytest.approx(ts.window_s - ts.busy_s)


def test_names():
    assert trace_reduce.op_name("%proximity.1 = f32[3584,3584]{1,0} custom-call(x)") == "proximity"
    assert trace_reduce.op_name("fusion.12") == "fusion"
    assert trace_reduce.module_name("jit__proximity_pallas_jit(7531459391941646858)") == "jit__proximity_pallas_jit"
    assert trace_reduce.union_length([(0, 10), (5, 15), (20, 25)]) == 20


def test_window_must_be_present():
    bad = _profile([("%copy = x", 0, 1)], [("span.hc", 0, 5)])
    with pytest.raises(ValueError, match="span.window"):
        trace_reduce.reduce_profile(bad)


CHIP = DATA / "boot_k512.xplane.pb.xz"


@pytest.mark.skipif(not CHIP.is_file(), reason="recorded chip trace not present")
def test_recorded_chip_trace():
    """A traced K=512 bootstrap run recorded on a TPU v5 lite
    (``record_trace.py``, kept xz-compressed): the reduction reads what
    that run reported."""
    from jax.profiler import ProfileData

    rec = json.loads((DATA / "boot_k512.json").read_text())
    profile = ProfileData.from_serialized_xspace(lzma.decompress(CHIP.read_bytes()))
    ts = trace_reduce.reduce_profile(profile, top=1000)
    line = rec["line"]
    assert ts.n_devices == 1
    assert ts.window_s == pytest.approx(line["device"]["window_s"])
    assert ts.busy_s == pytest.approx(line["device"]["busy_s"])
    assert 0 < ts.busy_s < ts.window_s
    # one proximity kernel call per bootstrap in the window
    assert ts.kernel_calls["proximity"] == line["attempted"]
    # every idle nanosecond of the window is attributed to some host activity
    assert sum(v for _, v in ts.idle_by_host) == pytest.approx(ts.window_s - ts.busy_s, rel=1e-9)
    # the device ops cover the busy time (ops may overlap, so at least busy)
    assert sum(v for _, v in ts.top_ops) >= ts.busy_s * (1 - 1e-9)
    assert dataclasses.asdict(ts)["kernel_s"] == pytest.approx(rec["summary"]["kernel_s"])
