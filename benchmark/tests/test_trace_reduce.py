"""trace_reduce on the small recorded trace kept beside it: busy union with a
nested event, an empty plane, a plane without operations, idle share. A second on the
CPU; needs no chip."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(
        os.path.join(HERE, "trace_small.txt"), window_s=1e-5, min_gap_s=0.0)


def test_union_counts_nested_and_overlapping_once():
    assert trace_reduce.union([(1, 5), (2, 4), (7, 8), (8, 9), (3, 6)]) \
        == [[1, 6], [7, 9]]
    assert trace_reduce.union([]) == []


def test_busy_per_plane(reduced):
    planes = reduced["planes"]
    assert set(planes) == {"/device:TPU:0", "/device:TPU:1", "/device:TPU:2"}
    assert planes["/device:TPU:0"] == {"events": 3,
                                       "busy_s": pytest.approx(5e-6)}
    assert planes["/device:TPU:1"]["busy_s"] == pytest.approx(3e-6)
    assert planes["/device:TPU:2"] == {"events": 0, "busy_s": 0.0}


def test_busy_is_averaged_over_chips_and_idle_share_follows(reduced):
    assert reduced["busy_s"] == pytest.approx(8e-6 / 3)
    assert reduced["idle_share"] == pytest.approx(1 - (8e-6 / 3) / 1e-5)


def test_breakdown(reduced):
    assert reduced["device_ops"][0] == ["while.1", pytest.approx(4e-6)]
    assert reduced["idle_gaps"] == [["while.1..copy.3", pytest.approx(2e-6)]]
    assert len(reduced["device_ops"]) <= 10


def test_gaps_shorter_than_the_floor_are_not_idle_gaps():
    out = trace_reduce.reduce_file(
        os.path.join(HERE, "trace_small.txt"), window_s=1e-5)
    assert out["idle_gaps"] == [] and out["busy_s"] > 0


def test_a_trace_with_no_device_plane_reads_nothing():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/host:CPU" }'))
    out = trace_reduce.reduce_profile(pd, window_s=1.0)
    assert out["planes"] == {} and out["busy_s"] == 0.0


def test_a_device_plane_without_an_operations_line_is_an_error():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: '
            '"XLA Modules" events { metadata_id: 1 duration_ps: 5 } } }'))
    with pytest.raises(ValueError, match="XLA Ops"):
        trace_reduce.reduce_profile(pd, window_s=1.0)


def test_recorded_trace_from_the_chip():
    """The first 400 operations of a real slice: one while loop of 332.9 us
    with its body's operations nested inside it, busy 333.9 us in all."""
    out = trace_reduce.reduce_file(
        os.path.join(HERE, "trace_recorded.txt"), window_s=1e-3)
    plane = out["planes"]["/device:TPU:0"]
    assert plane["events"] == 400
    assert plane["busy_s"] == pytest.approx(0.000333863, rel=1e-6)
    assert 100 * out["idle_share"] == pytest.approx(66.61, abs=0.01)
    assert out["device_ops"][0] == ["while.16", pytest.approx(0.000332855)]
    assert sum(out["op_s"].values()) > plane["busy_s"]   # nested, counted once
    assert len(out["op_s"]) == 6 and out["idle_gaps"] == []
