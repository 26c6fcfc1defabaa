"""The reduction from a profiler trace to busy time, idle share, kernel
time and the breakdown of idle gaps by host span."""
import pytest

import bench_tiny  # noqa: F401
from bench import trace


def test_union_and_gaps():
    iv = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (5.5, 5.7), (9.0, 12.0)]
    assert trace.union_seconds(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert trace.union_seconds(iv, 1.5, 5.5) == pytest.approx(2.0)
    assert trace.gaps(iv, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0),
                                         (6.0, 9.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_reduce_events_busy_ops_and_idle_attribution():
    dev = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("kern", 2.0, 4.0),
                             ("kern", 6.0, 7.0), ("outside", 20.0, 21.0)],
           "/device:TPU:1": [("kern", 1.0, 9.0)]}
    host = [("bench.window", 0.0, 10.0), ("bench.step", 0.5, 7.5),
            ("bench.wait", 7.5, 10.0), ("bench.submit", 0.0, 0.5)]
    s = trace.reduce_events(dev, host)
    assert s.window_s == 10.0 and s.devices == 2
    assert s.busy_s == pytest.approx((4.0 + 8.0) / 2)   # mean over chips
    assert s.op_seconds == {"fusion.1": 1.0, "kern": 11.0}
    assert s.device_ops[0] == ["kern", 11.0]
    idle = dict(s.idle_gaps)
    # chip 0 idle [0,1] step 0.5 / submit 0.5 -> one name takes the gap,
    # [4,6] step, [7,10] wait 2.5 of 3; chip 1 [0,1] and [9,10]
    assert sum(idle.values()) == pytest.approx((6.0 + 2.0) / 2)
    assert idle["bench.wait"] == pytest.approx((3.0 + 1.0) / 2)


def test_nested_ops_count_once_by_self_time():
    """A loop's event spans its body's ops: busy time is their union and
    each op keeps only its own time."""
    dev = {"/device:TPU:0": [
        ("while.4", 1.0, 9.0), ("paged_flash_decode_gqa.8", 2.0, 4.0),
        ("fusion.1", 4.0, 5.0), ("copy.2", 5.5, 6.0)]}
    s = trace.reduce_events(dev, [("bench.window", 0.0, 10.0)])
    assert s.busy_s == pytest.approx(8.0)
    assert s.op_seconds == pytest.approx({
        "while.4": 4.5, "paged_flash_decode_gqa.8": 2.0, "fusion.1": 1.0,
        "copy.2": 0.5})
    assert trace.op_name("%copy.2 = bf16[8]{0} copy(%x)") == "copy.2"


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": []}, [])
