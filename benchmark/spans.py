"""The program's own spans, summed over the audit replay's passes.

``run_audit`` records one ``audit.run`` trace per call in the process's
tracer (``cpzk_tpu.observability.tracing``): its stage spans, the dispatch
seam's spans, and per quantum an ``audit.quantum`` parent whose
``settled`` attribute counts the proofs it verified or rejected.  The
readers run in ``run.py``'s process after the driver, so they read that
tracer directly.  Only traces that finished ``complete`` count: every
pass of the run, inside the profiler's window or not, and not the
warm-up quantum (``checkpointed``).  A program that records no such
traces gives None."""

from __future__ import annotations


def passes() -> list:
    """The ``complete`` ``audit.run`` traces in the process's tracer."""
    try:
        from cpzk_tpu.observability.tracing import get_tracer
    except ImportError:
        return []
    return [t for t in get_tracer().completed()
            if t.name == "audit.run" and t.status == "complete"]


def settled(traces: list) -> int:
    """Proofs the traces' quanta verified or rejected."""
    return sum(s.attrs.get("settled", 0) for t in traces for s in t.spans
               if s.name == "audit.quantum")


def us_per_proof(names: tuple[str, ...]) -> float | None:
    """Seconds of every span named in ``names``, over every pass, per
    proof settled, in us."""
    traces = passes()
    n = settled(traces)
    if not n:
        return None
    return 1e6 * sum(t.stage_seconds(name) for t in traces
                     for name in names) / n
