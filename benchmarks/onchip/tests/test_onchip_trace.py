"""The reduction from a profiler trace to busy time, idle share, idle
gaps and top operations, on a small synthetic trace."""
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace  # noqa: E402
from harness.mfu import idle  # noqa: E402


def _plane(pid, name, line, events, names):
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
                  f"duration_ps: {d * 1000} }}\n" for m, s, d in events)
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0\n{evs}}}\n{meta}}}\n')


# times in ns.  Window [100, 1100).  Device ops: [0, 200) half outside the
# window, [150, 300) overlapping it, [500, 600), [590, 700) overlapping,
# [1050, 1300) crossing the close.  Busy inside: [100,300) + [500,700) +
# [1050,1100) = 450 ns; idle 550 ns: gaps [300,500) under a router step,
# [700,1050) under the client's span.
DEVICE = [(1, 0, 200), (2, 150, 150), (1, 500, 100), (3, 590, 110),
          (1, 1050, 250)]
HOST = [(1, 100, 1000), (2, 250, 300), (3, 650, 500)]
TEXT = (_plane(1, "/device:TPU:0", "XLA Ops", DEVICE,
               ["fusion.1", "copy.2", "dot.3"])
        + _plane(2, "/device:TPU:0 other", "Steps", [(1, 0, 2000)], ["x"])
        + _plane(3, "/host:CPU", "python", HOST,
                 ["bench:window", "bench:router_step", "bench:client"]))


def test_reduce_synthetic_trace():
    r = trace.reduce_profile(ProfileData.from_text_proto(TEXT))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["devices"] == 1
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((100 + 100 + 50) * 1e-9)
    assert ops["copy.2"] == pytest.approx(150e-9)
    assert ops["dot.3"] == pytest.approx(110e-9)
    assert r["device_ops"][0][0] == "fusion.1"
    # the gaps are shorter than the labelling threshold at ns scale
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(550e-9)


def test_idle_gaps_are_labelled_by_the_host_span(monkeypatch):
    monkeypatch.setattr(trace, "SHORT_S", 0.0)
    r = trace.reduce_profile(ProfileData.from_text_proto(TEXT))
    assert r["idle_gaps"] == [["client", pytest.approx(350e-9)],
                              ["router_step", pytest.approx(200e-9)]]

    class Run:
        pass
    run = Run()
    run.trace = r
    assert idle(run) == pytest.approx(55.0)


def test_union_clip_and_gaps():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert trace.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert trace.gaps(merged, 0, 12) == [(3, 5), (9, 12)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_a_trace_without_the_window_span_is_refused():
    text = _plane(1, "/device:TPU:0", "XLA Ops", DEVICE, ["a", "b", "c"])
    with pytest.raises(ValueError, match="bench:window"):
        trace.reduce_profile(ProfileData.from_text_proto(text))


def test_a_trace_with_no_device_reads_nothing():
    text = _plane(3, "/host:CPU", "python", HOST,
                  ["bench:window", "bench:router_step", "bench:client"])
    r = trace.reduce_profile(ProfileData.from_text_proto(text))
    assert r["devices"] == 0

    class Run:
        pass
    run = Run()
    run.trace = r
    assert idle(run) is None


def test_nested_ops_count_once_under_short_names():
    ops = [("%while.3 = (s32[]) while(..)", 0, 100),
           ("%fusion.1 = bf16[2] fusion(..), kind=kLoop", 10, 40),
           ("%copy.2 = bf16[2] copy(..)", 50, 60),
           ("%fusion.1 = bf16[2] fusion(..), kind=kLoop", 120, 130)]
    assert [trace.op_name(n) for n, _, _ in trace.leaves(ops)] == [
        "fusion.1", "copy.2", "fusion.1"]
    host = [(0, 200, trace.WINDOW)]
    r = trace.reduce_events({"/device:TPU:0": ops}, host)
    assert r["busy_s"] == pytest.approx(110e-9)
    assert dict(r["device_ops"]) == {"fusion.1": pytest.approx(40e-9),
                                     "copy.2": pytest.approx(10e-9)}
