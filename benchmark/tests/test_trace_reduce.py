"""trace_reduce.reduce over a hand-built trace: the busy union, per-program
device time and the labelling of idle gaps."""

import pytest

import trace_reduce

MS = 1_000_000  # ns


def _trace():
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit__combined_impl(7)", 0 * MS, 10 * MS),
            ("jit__each_shared_impl(9)", 20 * MS, 10 * MS),
            ("jit__combined_impl(7)", 40 * MS, 10 * MS),
        ]},
        {"name": "XLA Ops", "events": [     # inside the modules: not read
            ("fusion.1", 0 * MS, 3 * MS),
            ("fusion.3", 20 * MS, 4 * MS),
        ]},
    ]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "cpzk-lane-prep", "events": [
            ("cpzk.pad_and_pack", 11 * MS, 8 * MS),   # inside gap 10..20
            ("cpzk.unpack", 30 * MS, 2 * MS),         # part of gap 30..40
            ("cpzk.queue_wait", 31 * MS, 9 * MS),     # most of gap 30..40
            ("PjitFunction", 0, 1 * MS),              # not a cpzk span
        ]},
        {"name": "pjrt-tpu-tasks", "events": [
            ("H2D Dispatch", 52 * MS, 6 * MS),         # in the tail gap
        ]},
    ]}
    other = {"name": "/device:CUSTOM:Megascale Trace", "lines": []}
    return [host, device, other]


def test_busy_is_the_union_of_program_intervals():
    r = trace_reduce.reduce(_trace(), window_ns=60 * MS)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.030)      # 0-10, 20-30, 40-50
    assert r["window_s"] == pytest.approx(0.060)


def test_program_time_per_module():
    r = trace_reduce.reduce(_trace(), window_ns=60 * MS)
    assert r["programs"] == {"jit__combined_impl": pytest.approx(0.020),
                             "jit__each_shared_impl": pytest.approx(0.010)}
    assert r["device_ops"][0][0] == "jit__combined_impl"


def test_program_time_is_clipped_to_the_window():
    r = trace_reduce.reduce(_trace(), window_ns=45 * MS)
    assert r["programs"]["jit__combined_impl"] == pytest.approx(0.015)
    assert r["busy_s"] == pytest.approx(0.025)


def test_the_window_annotation_bounds_the_window():
    planes = _trace()
    # the host held the window open from 5 to 45 ms; an early host event
    # (0 ms) no longer anchors it
    planes[0]["lines"].append({"name": "python", "events": [
        (trace_reduce.WINDOW, 5 * MS, 40 * MS)]})
    r = trace_reduce.reduce(planes, window_ns=60 * MS)
    assert r["window_s"] == pytest.approx(0.040)
    assert r["programs"]["jit__combined_impl"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.020)      # 5-10, 20-30, 40-45
    assert not any(k.endswith(trace_reduce.WINDOW) for k, _ in r["idle_gaps"])


def test_gaps_are_labelled_by_the_host_span_overlapping_most():
    r = trace_reduce.reduce(_trace(), window_ns=60 * MS)
    gaps = dict(r["idle_gaps"])
    assert gaps["cpzk.pad_and_pack"] == pytest.approx(0.010)
    assert gaps["cpzk.queue_wait"] == pytest.approx(0.010)
    # the window's tail (50..60 ms) overlaps no cpzk span: a runtime event
    assert gaps["host: H2D Dispatch"] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(0.030)


def test_a_gap_nothing_overlaps():
    planes = _trace()
    planes[0]["lines"] = planes[0]["lines"][:1]   # no runtime events
    r = trace_reduce.reduce(planes, window_ns=60 * MS)
    assert dict(r["idle_gaps"])["host: nothing traced"] == pytest.approx(0.010)


def test_without_a_device_plane_nothing_is_busy():
    r = trace_reduce.reduce([_trace()[0], _trace()[2]], window_ns=60 * MS)
    assert r["devices"] == 0 and r["busy_s"] == 0.0 and r["idle_gaps"] == []


def test_a_recorded_cpu_trace_loads(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * x).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        with jax.profiler.TraceAnnotation("cpzk.device_dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    planes = trace_reduce.load(str(path))
    names = [e[0] for p in planes for line in p["lines"] for e in line["events"]]
    assert "cpzk.device_dispatch" in names
    (mark,) = [e for p in planes for line in p["lines"] for e in line["events"]
               if e[0] == trace_reduce.WINDOW]
    r = trace_reduce.reduce(planes)
    assert r["window_s"] == pytest.approx(mark[2] / 1e9)
    assert r["devices"] == 0  # XLA CPU: no device plane
