"""The readers of the serving worker's stage spans and runtime counters,
on hand-built flush records: the window's first flush left out where its
numbers reach back before the window, None with no flushes, and None for
records of a program that lacks the fields."""
from types import SimpleNamespace

import pytest

from tests.chipbench.test_chipbench_trace import reader

NAMES = ("host_gap_ms.tput", "engine_post_ms.tput", "window_compiles.tput",
         "gc_share.tput")


def _flush(gap_s, unpack_s, guard_s, compiles, gc_s):
    return SimpleNamespace(prep_s=0.001, dispatch_s=0.001, sync_s=0.004,
                           n_requests=16, wait_s=0.002, gap_s=gap_s,
                           idle_s=gap_s / 2, unpack_s=unpack_s,
                           guard_s=guard_s, compiles=compiles, gc_s=gc_s)


# the first flush's gap, compiles and GC reach back before the window
FLUSHES = [_flush(0.5, 0.001, 0.001, 7, 0.3),
           _flush(0.002, 0.0002, 0.0001, 0, 0.01),
           _flush(0.004, 0.0004, 0.0003, 1, 0.03)]
OBS = {"flushes": FLUSHES, "window_s": 10.0, "max_batch": 16}


@pytest.mark.parametrize("name, value", [
    ("host_gap_ms.tput", 3.0),                 # mean of 2 and 4 ms
    ("engine_post_ms.tput", 1.0),              # (2 + 0.3 + 0.7) / 3 ms
    ("window_compiles.tput", 1),
    ("gc_share.tput", 0.4),                    # 0.04 s of 10 s
])
def test_reader_on_flushes(name, value):
    assert reader(name)(OBS) == pytest.approx(value)


@pytest.mark.parametrize("name", NAMES)
def test_reader_none_without_flushes(name):
    assert reader(name)({"flushes": [], "window_s": 10.0}) is None
    assert reader(name)({"window_s": 10.0}) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_none_for_records_without_fields(name):
    old = [SimpleNamespace(prep_s=0.001, dispatch_s=0.001, sync_s=0.004,
                           n_requests=16, wait_s=0.002)] * 3
    assert reader(name)({"flushes": old, "window_s": 10.0}) is None


def test_gap_needs_a_second_flush():
    one = {"flushes": FLUSHES[:1], "window_s": 10.0}
    assert reader("host_gap_ms.tput")(one) is None
    assert reader("window_compiles.tput")(one) == 0
    assert reader("gc_share.tput")(one) == 0.0
    assert reader("engine_post_ms.tput")(one) == pytest.approx(2.0)
