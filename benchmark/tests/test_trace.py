"""The trace reducer on a made-up trace of two devices."""

import pytest

from benchmark import trace
from benchmark.trace import DeviceTrace, Trace

AR = "%all-reduce.1 = f32[1000]{0} all-reduce(f32[1000]{0} %x), replica_groups={}"
CONV = "%fusion.7 = bf16[8,64,56,56]{0,1,3,2:T(8,128)(2,1)} fusion(bf16[8,3,224,224]{3,2,1,0} %p), kind=kOutput"
POOL = ('%_.9 = bf16[512,7,7]{2,1,0:T(8,128)(2,1)} custom-call(bf16[512,13,13]{2,1,0} %pad.8), '
        'custom_call_target="tpu_custom_call"')


def made_up():
    d0 = DeviceTrace("/device:TPU:0", [
        (CONV, 1.0, 2.0), (POOL, 2.0, 2.5), (AR, 2.4, 3.0),   # 0.1 s hidden
        (CONV, 4.0, 5.0), ("%while.1 = () while(...)", 4.0, 5.5)])
    d1 = DeviceTrace("/device:TPU:1", [
        (CONV, 1.0, 2.0), (AR, 2.0, 2.2), (CONV, 4.0, 5.0)])
    return Trace([d0, d1], stamp_s=0.5)


def test_interval_arithmetic():
    assert trace.union([(3, 4), (1, 2), (1.5, 2.5), (5, 5)]) == \
        [(1, 2.5), (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert trace.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert trace.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_busy_union_and_idle_share():
    t = made_up()
    # device 0: [1,3] + [4,5.5] = 3.5 s; device 1: 1 + 0.2 + 1 = 2.2 s
    assert trace.busy_seconds(t, 0.0, 6.0) == pytest.approx((3.5 + 2.2) / 2)
    # clipped to a window: device 0 [2,3]+[4,4.5], device 1 [2,2.2]+[4,4.5]
    assert trace.busy_seconds(t, 2.0, 4.5) == pytest.approx((1.5 + 0.7) / 2)
    idle_share = 1 - trace.busy_seconds(t, 0.0, 6.0) / 6.0
    assert idle_share == pytest.approx(1 - 2.85 / 6)


def test_seconds_by_short_name():
    by = trace.seconds_by_name(made_up(), 0.0, 6.0)
    assert by["fusion.7 fusion bf16[8,64,56,56]"] == pytest.approx(2.0)
    assert by["_.9 pallas bf16[512,7,7]"] == pytest.approx(0.25)
    assert by["all-reduce.1 all-reduce f32[1000]"] == pytest.approx(0.4)
    assert trace.top(by, 1)[0][0] == "fusion.7 fusion bf16[8,64,56,56]"


def test_matching_seconds_merges_nesting():
    t = made_up()
    assert trace.matching_seconds(t, 0, 6, "tpu_custom_call") == \
        pytest.approx(0.25)
    # the while and the fusion it holds overlap on device 0: merged
    assert trace.matching_seconds(t, 0, 6, r"fusion\(|while\(") == \
        pytest.approx((1.0 + 1.5 + 2.0) / 2)


def test_exposed_collective_time():
    # device 0: all-reduce [2.4,3.0] with the pool running until 2.5
    # -> 0.5 s exposed; device 1: 0.2 s, nothing beside it
    assert trace.exposed_collective_seconds(made_up(), 0, 6) == \
        pytest.approx((0.5 + 0.2) / 2)


def test_idle_gaps_go_to_what_the_host_was_doing():
    t = made_up()
    phases = [("data_wait", 0.0, 0.8), ("in-step host", 0.8, 3.2),
              ("between steps", 3.2, 3.9)]
    got = trace.idle_gaps_by_phase(t, 0.0, 6.0, phases)
    # device 0 idle: [0,1], [3,4], [5.5,6]
    assert got["data_wait"] == pytest.approx(0.8)
    assert got["in-step host"] == pytest.approx(0.2 + 0.2)
    assert got["between steps"] == pytest.approx(0.7)
    assert got["unattributed"] == pytest.approx(0.1 + 0.5)
    assert sum(got.values()) == pytest.approx(6.0 - 3.5)


def test_short_name_of_a_plain_name():
    assert trace.short_name("copy.3") == "copy.3"


def test_collective_bytes_from_the_instruction_text():
    from benchmark.readers import coll_bytes

    start = ("%all-reduce-start.1 = (f32[1000]{0}, bf16[8,4]{1,0:T(8,128)(2,1)}) "
             "all-reduce-start(f32[1000]{0} %a, bf16[8,4]{1,0} %b), replica_groups={}")
    done = ("%all-reduce-done.1 = (f32[1000]{0}, bf16[8,4]{1,0}) "
            "all-reduce-done((f32[1000]{0}, bf16[8,4]{1,0}) %all-reduce-start.1)")
    assert coll_bytes.result_bytes(start) == 4000 + 64
    assert coll_bytes.result_bytes(AR) == 4000
    t = Trace([DeviceTrace("/device:TPU:0", [
        (start, 1.0, 1.1), (done, 1.5, 1.6), (AR, 2.0, 2.1),
        (CONV, 1.1, 1.5), (AR, 9.0, 9.1)])])
    ctx = {"chips": 4, "steps": 2, "trace": t, "lo": 0.0, "hi": 5.0}
    assert coll_bytes.read(ctx) == pytest.approx((4064 + 4000) / 2 / 1e6)
    assert coll_bytes.read(dict(ctx, chips=1)) is None
