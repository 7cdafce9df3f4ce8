"""The 4-chip audit cell, ``audit-log-mesh4.replay-1pct``, on 4 of the
CPU's virtual devices at a small size: its run reads correct, and not
correct with a fault planted under the timed path (faults.py) or the
control in the program's place; a traced run's span and counter readers
read the mesh's own spans and lanes; the cell reports the metrics
BENCHMARK.json lists for it; and each new reader computes its value from
built artifacts."""

import copy
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    # before anything imports JAX: the cell's run checks for its 4 chips
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

import harness  # noqa: E402
import traffic  # noqa: E402

CELL = "audit-log-mesh4.replay-1pct"
SEED = 2**31 + 2626
SIZES = {"records": 32, "statements": 4, "quantum": 8,
         "reject_frac": 0.25, "lie_frac": 0.0625}
NEW = ("mesh.host_us_per_proof.mesh4", "mesh.pad_share.mesh4")
SHARED = ("device.idle_share.bulk", "kernel.us_per_proof.bulk",
          "audit.host_us_per_proof.bulk",
          "dispatch.host_us_per_proof.bulk",
          "dispatch.execute_us_per_proof.bulk", "audit.wait_us_per_proof.bulk")


def _run(tmp_path, fault=None, control=False, trace=False):
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = copy.deepcopy(harness.load_config(cell["config"]))
    work = tmp_path / f"{fault}-{control}-{trace}"
    os.makedirs(work)
    run = harness.Run(workload=cell, config=config,
                      mix=traffic.load(cell["traffic"]), seed=SEED,
                      seconds=0.01, trace=trace, work_dir=str(work),
                      platform="cpu", fault=fault, sizes=SIZES)
    driver = harness.load_module("drivers", config["driver"])
    if not control:
        return driver.run(run)
    (row,) = driver.readings(run, [SEED])
    assert all(v <= lim for _, v, lim in row["program"]), row
    return row["control"]


def _correct(checks) -> bool:
    return all(v <= lim for _, v, lim in checks)


@pytest.mark.parametrize("fault", [None, "flip", "half", "control"])
def test_mesh_cell(tmp_path, fault):
    import jax

    assert jax.device_count() >= 4, "JAX started before XLA_FLAGS was set"
    got = _run(tmp_path, fault=None if fault == "control" else fault,
               control=fault == "control")
    checks = got if fault == "control" else got.checks
    assert _correct(checks) == (fault is None), checks
    if fault is None:
        assert got.device["count"] >= 4 and got.attempted > 0


def test_mesh_cell_refuses_per_backend_programs(tmp_path, monkeypatch):
    """A program without process-wide sharded programs exits before the
    window, and before it touches a device."""
    import jax

    from cpzk_tpu.parallel import mesh

    monkeypatch.delattr(mesh, "_EXES")
    monkeypatch.setattr(jax, "devices", lambda *a: pytest.fail("touched"))
    with pytest.raises(SystemExit, match="cannot run this cell"):
        _run(tmp_path)


def test_mesh_cell_traced_readers(tmp_path, capsys):
    from cpzk_tpu.observability.tracing import get_tracer
    from cpzk_tpu.ops import backend
    from cpzk_tpu.parallel import mesh

    get_tracer().clear()
    out = _run(tmp_path, trace=True)
    assert out.correct, out.checks
    # the prewarm left nothing for the window to compile
    assert "window: 0 jit misses booked, 0 programs compiled" in (
        capsys.readouterr().out)

    def read(name):
        return harness.load_module("metrics", name).read(out.artifacts)

    assert read("mesh.host_us_per_proof.mesh4") > 0
    q = SIZES["quantum"]
    terms = 4 * q + 2
    lanes = mesh._mesh_pad(4, backend._msm_shape(q)[1])[1] + q
    # every quantum pads alike, so the run's share is one quantum's
    assert read("mesh.pad_share.mesh4") == pytest.approx(
        (lanes - terms - q) / lanes)
    # the CPU has no device plane: the device readers find nothing
    assert read("kernel.us_per_proof.bulk") is None
    get_tracer().clear()


def test_mesh_cell_metrics():
    bench = harness.load_benchmark()
    e2e = {m["name"] for m in harness.metrics_of(bench, CELL, False)}
    assert e2e == {"verified_per_s", "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(bench, CELL, True)}
    assert layer == set(NEW + SHARED)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4
    config = harness.load_config(cell["config"])
    assert config["audit"]["mesh_devices"] == config["chips"] == 4


def test_span_reader_on_built_traces():
    from cpzk_tpu.observability.context import RequestContext, new_trace_id
    from cpzk_tpu.observability.tracing import get_tracer

    reader = harness.load_module("metrics", "mesh.host_us_per_proof.mesh4")
    tracer = get_tracer()
    tracer.clear()

    def one_pass(status, mesh_spans):
        tid = new_trace_id()
        tracer.start(RequestContext(trace_id=tid), "audit.run")
        for name, secs in mesh_spans:
            tracer.add_span(tid, name, 0.0, secs)
        for name in ("mesh.msm", "mesh.each"):  # not host stages
            tracer.add_span(tid, name, 0.0, 9.0)
        tracer.add_span(tid, "audit.quantum", 0.0, 1.0, settled=4096)
        tracer.finish(tid, status)

    try:
        assert reader.read({}) is None
        one_pass("complete", [("mesh.digits", 0.25), ("mesh.digits", 0.25)])
        one_pass("checkpointed", [("mesh.digits", 5.0)])  # the warm-up
        assert reader.read({}) == pytest.approx(1e6 * 0.5 / 4096)
    finally:
        tracer.clear()


def test_pad_share_reader_on_built_counters(monkeypatch):
    from cpzk_tpu.server import metrics

    reader = harness.load_module("metrics", "mesh.pad_share.mesh4")
    counts = {"term": 0.0, "pad": 0.0}
    monkeypatch.setattr(metrics, "read",
                        lambda name, kind="c", labels=None:
                        counts[labels["kind"]] if name == "mesh.lanes" else 0)
    assert reader.read({}) is None  # nothing counted: a program without them
    # one 4,096-row quantum: 16,386 terms in 18,432 MSM lanes, 4,096 rows
    counts.update(term=16_386 + 4_096, pad=2_046)
    assert reader.read({}) == pytest.approx(2_046 / 22_528)
