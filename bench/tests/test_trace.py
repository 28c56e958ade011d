"""The trace reduction, on its interval arithmetic and on a small trace
recorded on a TPU v5e by ``record_trace.py`` (``data/small.xplane.pb``:
four ``grid`` spans of four launches each, with host-only sleeps of 2 ms
inside each span and 1 ms between them)."""
import os

import pytest

from tracereduce import SPANS, _label, reduce_trace, union_length

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_of_overlapping_intervals():
    covered, holes = union_length([(2, 4), (3, 6), (8, 9), (0, 1)], 0, 10)
    assert covered == 1 + 4 + 1
    assert holes == [(1, 2), (6, 8), (9, 10)]


def test_union_clips_to_the_window():
    covered, holes = union_length([(-5, 2), (9, 20)], 0, 10)
    assert covered == 3 and holes == [(2, 9)]


def test_gap_labels_name_the_innermost_harness_span():
    spans = [("window", 0, 100), ("grid", 10, 50), ("allreduce", 20, 30)]
    assert _label(spans, 25) == "allreduce"
    assert _label(spans, 40) == "grid"
    assert _label(spans, 70) == "window"


@pytest.fixture(scope="module")
def small():
    return reduce_trace(SMALL)


def test_recorded_trace_counts_every_launch(small):
    assert small.devices == 1
    assert list(small.module_calls.values()) == [16]


def test_recorded_trace_busy_is_the_union_of_its_ops(small):
    # The launches do not overlap, so their union is the programs' time.
    assert small.busy_s == pytest.approx(sum(small.module_s.values()),
                                         rel=1e-3)
    assert 0 < small.busy_s < small.window_s
    assert small.idle_share == pytest.approx(
        1 - small.busy_s / small.window_s)


def test_recorded_trace_gaps_cover_the_idle_time(small):
    idle = sum(s for _, s in small.gaps)
    assert idle == pytest.approx(small.window_s - small.busy_s, rel=1e-6)
    assert {label for label, _ in small.gaps} <= set(SPANS)
    # The host's 3 ms of sleep between the four bursts of launches.
    longest = sorted((s for _, s in small.gaps), reverse=True)[:3]
    assert all(s > 3e-3 for s in longest)
    assert small.breakdown()["idle_gaps"][0][1] == longest[0]
