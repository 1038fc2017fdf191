"""The trace reduction on a small trace recorded on one TPU v5e: a
2048 x 2048 float32 program called three times, each call followed by a
20 ms host sleep, all inside a ``bench.window`` annotation."""
from pathlib import Path

import pytest

from bench import trace as T

SMALL = Path(__file__).parent / "data" / "small_trace.xplane.pb"

# The 20 device operations that lie inside the window do not overlap
# (checked by hand from the trace's "XLA Ops" line); their durations add
# up to 365,937 ns. The window annotation lasts 67,062,767 ns.
BUSY_NS = 365_937
WINDOW_NS = 67_062_767


@pytest.fixture(scope="module")
def small():
    return T.reduce_trace(str(SMALL))


def test_busy_and_window(small):
    assert small.n_devices == 1
    assert small.busy_s == pytest.approx(BUSY_NS / 1e9, abs=1e-12)
    assert small.window_s == pytest.approx(WINDOW_NS / 1e9, abs=1e-12)
    assert len(small.ops) == 20


def test_device_ops_add_up(small):
    names = [name for name, _ in small.device_ops]
    assert names[0] == "fusion"
    assert sum(s for _, s in small.device_ops) == pytest.approx(
        BUSY_NS / 1e9, abs=1e-12)


def test_idle_gaps_go_to_the_host_sleep(small):
    # every gap's middle falls inside one of the three 20 ms sleeps
    assert small.idle_gaps == [["python3:bench.host_sleep",
                                pytest.approx((WINDOW_NS - BUSY_NS) / 1e9,
                                              abs=1e-12)]]


def test_union_clips_and_merges():
    covered, gaps = T.union_ns([(0, 10), (5, 15), (20, 30), (40, 60)], 2, 50)
    assert covered == (15 - 2) + (30 - 20) + (50 - 40)
    assert gaps == [(15, 20), (30, 40)]


def test_gap_goes_to_innermost_host_event():
    events = [(0, 100, "main:outer"), (10, 30, "main:inner"),
              (50, 60, "worker:short"), (110, 140, "worker:wait")]
    got = T._attribute([(12, 18), (40, 45), (52, 58), (120, 130),
                        (150, 160)], events)
    assert dict(got) == {"main:inner": 6, "main:outer": 5,
                         "worker:short": 6, "worker:wait": 10,
                         T.UNTRACED: 10}
    # the window's own thread goes first: a worker's shorter event only
    # where the main thread has none
    main = [e for e in events if e[2].startswith("main")]
    got = T._attribute([(52, 58), (120, 130)], events, main)
    assert dict(got) == {"main:outer": 6, "worker:wait": 10}


def test_device_ops_count_leaf_operations():
    # a while loop's event spans the two fusions of its body
    events = [(0, 100, "%while.8 = ..."), (5, 40, "%fusion.1 = ..."),
              (50, 45, "%fusion.2 = ..."), (120, 10, "%copy.3 = ...")]
    assert [t for _, _, t in T._leaves(events)] == [
        "%fusion.1 = ...", "%fusion.2 = ...", "%copy.3 = ..."]


def test_kernel_ops_need_a_pallas_call(small):
    assert T.kernel_ops(small, "fusion") == []
    assert T.kernel_ops(None, "codebook_lookup") == []


def test_missing_window_raises():
    with pytest.raises(ValueError, match="no host annotation"):
        T.reduce_trace(str(SMALL), window="bench.absent")
