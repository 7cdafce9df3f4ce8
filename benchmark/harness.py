"""What every driver shares: the run's inputs, its outcome, finding files
by name, the device check, and the profiler's options and reduction.

A cell is ``<config>.<traffic>``; its configuration is
``benchmark/configs/<config>.json``, its mix ``benchmark/traffic/<mix>.json``,
the driver the configuration names ``benchmark/drivers/<driver>.py``, and
each metric ``benchmark/metrics/<metric>.py`` (a ``read(artifacts)``
function).  Nothing here names a cell, so a later PR adds one with files
and ``BENCHMARK.json`` entries alone.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def use_checkout_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    unbounded, for this process and any it starts: whatever cache
    the machine's environment names is not this checkout's, and a bounded
    one evicts the prewarmed programs (PR 22: under a 192 MiB cap a warm
    prewarm was as slow as a cold one).  Call before anything imports JAX."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


class NoDevice(RuntimeError):
    """The cell's chips are not there: exit non-zero, print no result."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def load_config(name: str) -> dict:
    return load_json(HERE, "configs", f"{name}.json")


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics,
    or with ``trace`` its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in names
                                 else [])]


@dataclass
class Run:
    """One run's inputs."""

    workload: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    work_dir: str
    t0: float = field(default_factory=time.monotonic)
    platform: str = "tpu"       # what the device must be (tests: "cpu")
    fault: str | None = None    # tests only: see faults.py
    sizes: dict = field(default_factory=dict)  # tests only: smaller sizes

    def note(self, msg: str) -> None:
        print(f"# {msg}", flush=True)

    def size(self, key: str):
        return self.sizes.get(key, self.config[key])


@dataclass
class Outcome:
    device: dict
    attempted: int
    failed: int
    checks: list          # (name, value, limit): correct iff value <= limit
    artifacts: dict
    breakdown: dict | None = None

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.checks)


def check_device(run: Run, dev: dict) -> None:
    """Raise NoDevice unless the run got the platform and chips it needs
    and the chip is in the peaks table.  The driver then uses exactly the
    cell's ``chips``, however many more the host shows."""
    if dev["platform"] != run.platform:
        raise NoDevice(f"no TPU found: JAX's platform is {dev['platform']!r}")
    if dev["count"] < run.workload["chips"]:
        raise NoDevice(f"the cell needs {run.workload['chips']} chips, JAX "
                       f"reports {dev['count']}")
    if run.platform == "tpu":
        peaks = load_json(HERE, "peaks.json")["devices"]
        if dev["kind"] not in peaks:
            raise NoDevice(f"device kind {dev['kind']!r} is not in "
                           "benchmark/peaks.json")


def build_native() -> float:
    """Build the program's native core where it is stale (the checkout
    builds it once; later runs find it); returns the seconds taken."""
    t = time.monotonic()
    subprocess.run(["make", "-s"], cwd=os.path.join(ROOT, "cpzk_tpu", "native"),
                   check=True, timeout=600)
    return time.monotonic() - t


# libtpu's device trace mode for traced runs (``tpu_trace_mode``; PERF.md
# section 3 gives what each mode costs the kernels it times)
TPU_TRACE_MODE = "TRACE_ONLY_XLA"


def profile_options():
    """Host annotations and the device's programs, without JAX's Python
    function tracer (it would trace every call of the host pipeline)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    if TPU_TRACE_MODE:
        opts.advanced_configuration = {"tpu_trace_mode": TPU_TRACE_MODE}
    return opts


def reduce_trace(trace_dir: str, bounds_ns: tuple[int, int] | None) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir`` in a child that
    holds no chip (``JAX_PLATFORMS=cpu``); None when no trace was written."""
    found = []
    for d, _, files in os.walk(trace_dir):
        found += [os.path.join(d, f) for f in files if f.endswith(".xplane.pb")]
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    cmd = [sys.executable, os.path.join(HERE, "trace_reduce.py"), path]
    if bounds_ns:
        cmd += ["--window-ns", str(bounds_ns[0]), str(bounds_ns[1])]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(cmd, env=env, check=True, capture_output=True,
                         text=True, timeout=240)
    return json.loads(out.stdout.strip().splitlines()[-1])


def trace_device(dev: dict, reduced: dict | None) -> tuple[dict, dict | None]:
    """``device`` gains ``busy_s``/``window_s``; returns the breakdown."""
    if reduced is None:
        return dev, None
    dev = dict(dev, busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    return dev, {"device_ops": reduced["device_ops"][:10],
                 "idle_gaps": reduced["idle_gaps"][:10]}
