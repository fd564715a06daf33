"""trace_reduce on the traces kept beside it: busy union with a nested event,
an empty plane, a plane without operations, idle share (``trace_small.txt``,
``trace_recorded.txt``: no session plane, so the window is given); a window
marked in Unix time placed by the profiler's own collection span and the
operations clipped to it (``trace_span.txt``); run.py's refusal of a trace
that places no window. A second
on the CPU; needs no chip."""

import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import trace_reduce  # noqa: E402


SMALL = os.path.join(HERE, "trace_small.txt")
SPAN = os.path.join(HERE, "trace_span.txt")
RECORDED_AT = 71329572   # trace_recorded.txt's first operation, ns
NO_SPAN = 'planes { id: 1 name: "/host:CPU" }'
SESSION = ('planes { id: 9 name: "Task Environment" '
           'stats { metadata_id: 1 uint64_value: %d } '
           'stats { metadata_id: 2 uint64_value: %d } '
           'stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }'
           ' stat_metadata { key: 2 value { id: 2 name: "profile_stop_time" } '
           '} }')


def profile_of(text: str):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(SMALL, (0, 10_000), min_gap_s=0.0)


@pytest.fixture(scope="module")
def spanned():
    return trace_reduce.reduce_file(SPAN, min_gap_s=0.0)


def test_union_counts_nested_and_overlapping_once():
    assert trace_reduce.union([(1, 5), (2, 4), (7, 8), (8, 9), (3, 6)]) \
        == [[1, 6], [7, 9]]
    assert trace_reduce.union([]) == []


def test_busy_per_plane(reduced):
    planes = reduced["planes"]
    assert set(planes) == {"/device:TPU:0", "/device:TPU:1", "/device:TPU:2"}
    assert planes["/device:TPU:0"] == {
        "events": 3, "busy_s": pytest.approx(5e-6), "clipped_s": 0.0,
        "outside": 0, "first_s": pytest.approx(1e-6),
        "last_s": pytest.approx(8e-6)}
    assert planes["/device:TPU:1"]["busy_s"] == pytest.approx(3e-6)
    assert planes["/device:TPU:2"] == {
        "events": 0, "busy_s": 0.0, "clipped_s": 0.0, "outside": 0,
        "first_s": None, "last_s": None}


def test_busy_is_averaged_over_chips_and_idle_share_follows(reduced):
    assert reduced["busy_s"] == pytest.approx(8e-6 / 3)
    assert reduced["idle_share"] == pytest.approx(1 - (8e-6 / 3) / 1e-5)


def test_breakdown(reduced):
    assert reduced["device_ops"][0] == ["while.1", pytest.approx(4e-6)]
    assert reduced["idle_gaps"] == [["while.1..copy.3", pytest.approx(2e-6)]]
    assert len(reduced["device_ops"]) <= 10


def test_gaps_shorter_than_the_floor_are_not_idle_gaps():
    out = trace_reduce.reduce_file(SMALL, (0, 10_000))
    assert out["idle_gaps"] == [] and out["busy_s"] > 0


def test_a_trace_with_no_device_plane_reads_nothing():
    out = trace_reduce.reduce_profile(profile_of(NO_SPAN), (0, 1e9))
    assert out["planes"] == {} and out["busy_s"] == 0.0
    assert out["window_s"] == 1.0 and out["span_unix_s"] is None


def test_a_device_plane_without_an_operations_line_is_an_error():
    pd = profile_of(
        'planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: '
        '"XLA Modules" events { metadata_id: 1 duration_ps: 5 } } }')
    with pytest.raises(ValueError, match="XLA Ops"):
        trace_reduce.reduce_profile(pd, (0, 1e9))


def test_recorded_trace_from_the_chip():
    """The first 400 operations of a real slice: one while loop of 332.9 us
    with its body's operations nested inside it, busy 333.9 us in all."""
    out = trace_reduce.reduce_file(
        os.path.join(HERE, "trace_recorded.txt"),
        (RECORDED_AT, RECORDED_AT + 1_000_000))
    plane = out["planes"]["/device:TPU:0"]
    assert plane["events"] == 400
    assert plane["busy_s"] == pytest.approx(0.000333863, rel=1e-6)
    assert 100 * out["idle_share"] == pytest.approx(66.61, abs=0.01)
    assert out["device_ops"][0] == ["while.16", pytest.approx(0.000332855)]
    assert sum(out["op_s"].values()) > plane["busy_s"]   # nested, counted once
    assert len(out["op_s"]) == 6 and out["idle_gaps"] == []
    assert plane["clipped_s"] == 0.0 and plane["outside"] == 0
    assert plane["first_s"] == 0.0


# -- the window from the trace's own collection span --------------------------


START = 1_700_000_000_000_000_000   # trace_span.txt's profile_start_time


def test_without_marks_the_window_is_the_collection_span(spanned):
    assert trace_reduce.collection_span(trace_reduce.load(SPAN)) == (
        START, START + 10_000)
    assert spanned["window_s"] == 1e-5
    assert spanned["span_unix_s"] == (1_700_000_000.0, 1_700_000_000.00001)


def test_marks_are_placed_by_the_spans_start():
    """Marks in Unix time read the same as their interval on the device's
    clock, whose zero is the span's start."""
    marked = trace_reduce.reduce_file(SPAN,
                                      marks=(START + 3_000, START + 9_000))
    given = trace_reduce.reduce_file(SPAN, (3_000, 9_000))
    assert marked["span_unix_s"] == (1_700_000_000.0, 1_700_000_000.00001)
    for key in ("planes", "busy_s", "window_s", "idle_share", "op_s"):
        assert marked[key] == given[key]
    assert marked["window_s"] == marked["busy_s"] == 6e-6
    assert marked["planes"]["/device:TPU:0"]["outside"] == 4


@pytest.mark.parametrize("marks", [
    (START - 1, START + 5_000), (START + 5_000, START + 10_001),
    (START + 5_000, START + 5_000), (5_000, 9_000)])
def test_marks_that_are_no_window_inside_the_span_raise(marks):
    with pytest.raises(ValueError, match="not the profiler's"):
        trace_reduce.reduce_file(SPAN, marks=marks)


def test_a_device_that_never_idles_reads_exactly_its_window(spanned):
    """Operations that start before the span, end after it and cover it
    whole: what lies outside is clipped, so busy is the window to the
    picosecond and never more, plane by plane and in the mean."""
    for plane in spanned["planes"].values():
        assert plane["busy_s"] == spanned["window_s"]
        assert plane["first_s"] == 0.0 and plane["last_s"] == 1e-5
    assert spanned["busy_s"] == spanned["window_s"]
    assert spanned["idle_share"] == 0.0 and spanned["idle_gaps"] == []


def test_what_the_clipping_took_is_said(spanned):
    planes = spanned["planes"]
    assert planes["/device:TPU:0"]["events"] == 5
    assert planes["/device:TPU:0"]["clipped_s"] == 6e-6
    assert planes["/device:TPU:0"]["outside"] == 2
    assert planes["/device:TPU:1"]["clipped_s"] == 3e-6
    assert planes["/device:TPU:1"]["outside"] == 0


def test_operations_count_the_part_inside(spanned):
    """while.1 runs 5 us, 3 of them inside; fusion.4 and fusion.5 lie wholly
    outside and are no operation of the window."""
    assert spanned["op_s"] == {"while.1": 3e-6, "fusion.2": 6e-6,
                               "copy.3": 1e-6, "while.9": 1e-5}
    assert spanned["device_ops"][0] == ["while.9", 1e-5]


def test_a_trace_without_the_span_raises_unless_the_interval_is_given():
    with pytest.raises(ValueError, match="profile_start_time"):
        trace_reduce.reduce_file(SMALL)
    assert trace_reduce.collection_span(trace_reduce.load(SMALL)) is None
    with pytest.raises(ValueError, match="profile_start_time"):
        trace_reduce.reduce_file(SMALL, marks=(START, START + 10_000))
    assert trace_reduce.reduce_file(SMALL, (0, 10_000))["window_s"] == 1e-5


def test_a_gap_is_of_the_clipped_operations():
    """An interval that ends inside the small trace's gap: the gap reaches
    the window's edge and no further, and no gap is named beyond it."""
    out = trace_reduce.reduce_file(SMALL, (0, 6_000), min_gap_s=0.0)
    assert out["planes"]["/device:TPU:0"]["outside"] == 1
    assert out["idle_gaps"] == []
    assert out["planes"]["/device:TPU:0"]["last_s"] == pytest.approx(5e-6)


def test_clocks_that_do_not_line_up_show_as_seconds_clipped():
    """Device timestamps in Unix time where the session's are relative: every
    operation falls outside, nothing reads as a quiet device in silence."""
    start = 1_700_000_000_000_000_000
    text = (SESSION % (start, start + 3_000_000_000)) + (
        ' planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: "XLA Ops" '
        'timestamp_ns: %d events { metadata_id: 1 offset_ps: 0 duration_ps: '
        '2000000000000 } } event_metadata { key: 1 value { id: 1 name: '
        '"while.1" } } }' % start)
    out = trace_reduce.reduce_profile(profile_of(text))
    plane = out["planes"]["/device:TPU:0"]
    assert out["busy_s"] == 0.0 and out["window_s"] == 3.0
    assert plane["outside"] == 1
    assert plane["clipped_s"] == pytest.approx(2.0, abs=1e-6)


def test_a_span_that_ends_before_it_starts_is_no_span():
    pd = profile_of(SESSION % (2_000, 1_000))
    assert trace_reduce.collection_span(pd) is None


# -- run.py: no share on two clocks --------------------------------------------


def written(tmp_path, text: str) -> str:
    from jax.profiler import ProfileData
    tdir = tmp_path / "plugins" / "profile" / "made"
    tdir.mkdir(parents=True)
    (tdir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_run_refuses_a_trace_without_the_span(tmp_path, capsys):
    import run
    with open(SMALL) as f:
        tdir = written(tmp_path, f.read())
    assert run.reduce_trace(tdir, (START, START + 10_000), "") is None
    out, err = capsys.readouterr()
    assert err.count("\n") == 1 and "no collection span" in err
    assert not os.path.exists(tdir)


def test_run_refuses_marks_the_trace_does_not_hold(tmp_path, capsys):
    """The host's clock a second off the profiler's: no window, one line."""
    import run
    with open(SPAN) as f:
        tdir = written(tmp_path, f.read())
    late = START + 1_000_000_000
    assert run.reduce_trace(tdir, (late, late + 5_000), "") is None
    err = capsys.readouterr().err
    assert err.count("\n") == 1 and "not the profiler's" in err


def test_run_refuses_a_profiler_that_wrote_no_trace(tmp_path, capsys):
    import run
    assert run.reduce_trace(str(tmp_path), (START, START + 1), "") is None
    assert capsys.readouterr().err == \
        "run.py: the profiler wrote no .xplane.pb\n"


def test_run_reduces_and_keeps_a_trace_with_the_span(tmp_path, capsys):
    import run
    with open(SPAN) as f:
        tdir = written(tmp_path / "t", f.read())
    trace = run.reduce_trace(tdir, (START + 1_000, START + 9_000),
                             str(tmp_path / "kept"))
    assert trace["busy_s"] == trace["window_s"] == 8e-6
    assert os.listdir(tmp_path / "kept") == ["host.xplane.pb"]
    assert capsys.readouterr().err == "" and not os.path.exists(tdir)
    shutil.rmtree(tmp_path / "kept")
