"""Self time and counts of the program's spans, on hand-built records."""
import dataclasses

import program_spans


@dataclasses.dataclass
class Rec:
    name: str
    id: int
    parent: object
    start_ns: int
    end_ns: int
    counts: dict = dataclasses.field(default_factory=dict)


class Run:
    steps = 2


def _records():
    # a [0, 100) holds b [10, 40) and c [50, 90); b holds d [20, 25);
    # a second a [200, 260) holds b [210, 220)
    return [
        Rec("d", 4, 2, 20, 25),
        Rec("b", 2, 1, 10, 40, {"n": 3}),
        Rec("c", 3, 1, 50, 90),
        Rec("a", 1, None, 0, 100),
        Rec("b", 6, 5, 210, 220, {"n": 4}),
        Rec("a", 5, None, 200, 260),
    ]


def test_self_time_of_nested_spans():
    recs = _records()
    assert program_spans.self_ns(recs, "a") == (100 - 30 - 40) + (60 - 10)
    assert program_spans.self_ns(recs, "b") == (30 - 5) + 10


def test_self_time_of_siblings_and_leaves():
    recs = _records()
    assert program_spans.self_ns(recs, "c") == 40
    assert program_spans.self_ns(recs, "d") == 5


def test_absent_span_reads_none():
    recs = _records()
    assert program_spans.self_ns(recs, "e") is None
    assert program_spans.counted(recs, "e", "n") is None
    assert program_spans.counted(recs, "a", "n") is None


def test_counts_sum_over_spans():
    assert program_spans.counted(_records(), "b", "n") == 7


def test_per_step_readings(monkeypatch):
    monkeypatch.setattr(program_spans, "program_records", _records)
    assert program_spans.self_ms_per_step(Run, "c") == 1e-6 * 40 / 2
    assert program_spans.count_per_step(Run, "b", "n") == 3.5
    assert program_spans.self_ms_per_step(Run, "e") is None


def test_program_without_spans_reads_none(monkeypatch):
    monkeypatch.setattr(program_spans, "program_records", lambda: None)
    assert program_spans.self_ms_per_step(Run, "a") is None
    assert program_spans.count_per_step(Run, "b", "n") is None
