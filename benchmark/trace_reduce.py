"""The one reduction from a profiler trace to device numbers.

``reduce(planes, window_ns)`` works on plain data, so the tests can hand it
a built trace: a list of planes ``{"name", "lines": [{"name", "events":
[(name, start_ns, duration_ns), ...]}]}``.  ``load(path)`` turns an
``.xplane.pb`` into that shape with ``jax.profiler.ProfileData``.

- busy: the union of the intervals in which the device ran a program (the
  ``XLA Modules`` line; without one, every event of the plane), averaged
  over the device planes.  Gaps between the ops inside a program count as
  busy: they are the kernel's own time (``kernel.*`` metrics), not idle;
- programs: device seconds per program (a module's name less its
  ``(id)`` suffix), clipped to the window as busy is;
- idle gaps: each stretch of the window in which the device ran nothing,
  labelled by the ``cpzk.*`` host annotation (``TraceAnnotation``) that
  overlaps it most, else ``host: <event>`` for the host runtime event
  that does, else ``host: nothing traced``; summed per label.

Run as a script (``python trace_reduce.py TRACE [--window-ns A B]``) it
prints one JSON line; the harness runs it in a child with
``JAX_PLATFORMS=cpu`` so that it never holds a chip.
"""

from __future__ import annotations

import argparse
import json
import re

MODULES_LINE = "XLA Modules"
# the host annotation a traced run holds open over its window; the trace's
# own clock then bounds the window (the first event can come well before
# start_trace returns: PR 22 saw a window so anchored lose its last program)
WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def _is_device(plane: dict) -> bool:
    """An accelerator core's plane (not ``/device:CUSTOM:...`` and the
    like, which hold no programs: PR 22's first traces counted one and
    halved the busy time)."""
    return bool(_DEVICE.match(plane["name"]))


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _label(a: float, b: float, spans: list, host: list) -> str:
    """What the host was doing in the gap [a, b]."""
    for events, prefix in ((spans, ""), (host, "host: ")):
        best, label = 0.0, None
        for s0, s1, n in events:
            ov = _overlap(a, b, s0, s1)
            if ov > best:
                best, label = ov, n
        if label is not None:
            return prefix + label
    return "host: nothing traced"


def reduce(planes: list[dict], window_ns: float | None = None) -> dict:
    """Busy and idle seconds, per-program device seconds, labelled gaps.

    The window is the ``WINDOW`` annotation's span where the trace holds
    one; else it starts at the earliest event and lasts ``window_ns``
    (default: the span of all events)."""
    events = [e for p in planes for line in p["lines"] for e in line["events"]]
    if not events:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0, "programs": {},
                "device_ops": [], "idle_gaps": []}
    marks = [(s, s + d) for p in planes if not _is_device(p)
             for line in p["lines"] for n, s, d in line["events"] if n == WINDOW]
    if marks:
        t0, end = marks[0]
    else:
        t0 = min(e[1] for e in events)
        end = t0 + (window_ns or max(e[1] + e[2] for e in events) - t0)
    span = end - t0
    devices = [p for p in planes if _is_device(p)]
    spans, host = [], []
    for p in planes:
        if _is_device(p):
            continue
        for line in p["lines"]:
            for n, s, d in line["events"]:
                if n != WINDOW:
                    (spans if n.startswith("cpzk") else host).append((s, s + d, n))
    busy_total = 0.0
    programs: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for p in devices:
        lines = {line["name"]: line["events"] for line in p["lines"]}
        runs = lines.get(MODULES_LINE) or [e for evs in lines.values() for e in evs]
        busy = _union([(max(s, t0), min(s + d, end)) for _, s, d in runs
                       if s < end and s + d > t0])
        busy_total += sum(b - a for a, b in busy)
        for n, s, d in runs:
            inside = _overlap(s, s + d, t0, end)
            if inside > 0:
                name = _SUFFIX.sub("", n)
                programs[name] = programs.get(name, 0.0) + inside / 1e9
        edges = [t0] + [x for iv in busy for x in iv] + [end]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label = _label(a, b, spans, host)
                gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9 / len(devices)
    n = max(1, len(devices))
    return {
        "busy_s": busy_total / 1e9 / n,
        "window_s": span / 1e9,
        "devices": len(devices),
        "programs": programs,
        "device_ops": sorted(([k, v] for k, v in programs.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1]),
    }


def load(path: str) -> list[dict]:
    """The planes ``reduce`` reads: a device plane's ``XLA Modules`` line
    (its per-op lines hold millions of events and are skipped), every host
    line."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for p in data.planes:
        device = _is_device({"name": p.name})
        lines = [{"name": line.name,
                  "events": [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events]}
                 for line in p.lines
                 if not device or line.name == MODULES_LINE]
        out.append({"name": p.name, "lines": lines})
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("trace")
    p.add_argument("--window-ns", nargs=2, type=int, default=None,
                   help="wall-clock ns at the trace's start and stop")
    args = p.parse_args()
    window = args.window_ns[1] - args.window_ns[0] if args.window_ns else None
    print(json.dumps(reduce(load(args.trace), window)))


if __name__ == "__main__":
    main()
