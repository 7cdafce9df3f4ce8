"""Batch-verification benchmarks: the END-TO-END ``BatchVerifier`` path.

Mirror of the reference's criterion suite ``benches/batch_verification.rs``
(batch-vs-individual at n in {1,2,5,10,20,50,100} — ``:9-67``; with
transcript contexts — ``:69-113``; mixed validity — ``:115-150``; add()
cost — ``:152-172``), measured here end to end: challenge re-derivation,
random alpha draws, limb marshalling, and the backend pass are ALL inside
the timed region — this is the number a serving operator sees per batch,
complementing the device-kernel-only bench.py headline.

Backends: cpu (host oracle, default) and tpu (JAX data plane; pass --tpu,
add --platform cpu to force the JAX CPU backend for smoke runs).

Prints one JSON line per config: {"name", "n", "value", "unit"}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZES = (1, 2, 5, 10, 20, 50, 100)


def best_of(fn, runs: int = 3) -> tuple[float, float]:
    """(best seconds, spread) over ``runs`` calls; spread = max-min is the
    run's own noise bound, carried into PerfSnapshot entries so the
    regression gate widens itself on noisy machines."""
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times), max(times) - min(times)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tpu", action="store_true", help="also bench the TPU backend")
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform (e.g. cpu) for --tpu smoke runs")
    ap.add_argument("--sizes", default=",".join(map(str, SIZES)))
    ap.add_argument("--runs", type=int, default=3,
                    help="timed repetitions per config (best-of)")
    ap.add_argument("--snapshot", default=None,
                    help="also write a cpzk-perf-snapshot JSON here (the "
                         "CI regression gate's input — see "
                         "cpzk_tpu.observability.regress)")
    args = ap.parse_args()
    sizes = [int(s) for s in args.sizes.split(",")]

    from cpzk_tpu import (
        BatchVerifier,
        Parameters,
        Prover,
        SecureRng,
        Transcript,
        Verifier,
        Witness,
    )
    from cpzk_tpu.core.ristretto import Ristretto255

    rng = SecureRng()
    params = Parameters.new()
    nmax = max(sizes)
    rows = []
    for i in range(nmax):
        prover = Prover(params, Witness(Ristretto255.random_scalar(rng)))
        ctx = f"challenge-{i}".encode()
        t = Transcript()
        t.append_context(ctx)
        rows.append((prover.statement, prover.prove_with_transcript(rng, t), ctx))

    backends: list[tuple[str, object]] = [("cpu", None)]  # None -> CpuBackend default
    if args.tpu:
        if args.platform:
            import jax

            jax.config.update("jax_platforms", args.platform)
        from cpzk_tpu import jaxrt
        from cpzk_tpu.ops.backend import TpuBackend

        jaxrt.enable_compile_cache()

        backends.append(("tpu", TpuBackend()))

    results = []
    for n in sizes:
        # individual: n full verify_with_transcript passes
        def individual(n=n):
            for st, pr, ctx in rows[:n]:
                t = Transcript()
                t.append_context(ctx)
                Verifier(params, st).verify_with_transcript(pr, t)

        results.append(("individual", "host", n, *best_of(individual, args.runs)))

        for bname, backend in backends:
            def batched(n=n, backend=backend):
                bv = BatchVerifier(backend=backend)
                for st, pr, ctx in rows[:n]:
                    bv.add_with_context(params, st, pr, ctx)
                assert bv.verify(rng) == [None] * n

            if bname == "tpu":
                batched()  # warm the jit cache outside the timed region
            results.append(("batch_e2e", bname, n, *best_of(batched, args.runs)))

        # mixed validity: one mismatched row forces the fallback pass
        if n >= 2:
            def mixed(n=n):
                bv = BatchVerifier()
                for st, pr, ctx in rows[: n - 1]:
                    bv.add_with_context(params, st, pr, ctx)
                bv.add_with_context(params, rows[0][0], rows[1][1], rows[0][2])
                res = bv.verify(rng)
                assert res[-1] is not None

            results.append(
                ("batch_mixed_validity", "cpu", n, *best_of(mixed, args.runs))
            )

    # add() cost (validation on add), reference batch_verification.rs:152-172
    def add_cost():
        bv = BatchVerifier()
        for st, pr, ctx in rows[: min(100, nmax)]:
            bv.add_with_context(params, st, pr, ctx)

    results.append(
        ("batch_add", "host", min(100, nmax), *best_of(add_cost, args.runs))
    )

    for name, backend, n, secs, spread in results:
        print(
            json.dumps(
                {
                    "name": name,
                    "backend": backend,
                    "n": n,
                    "value": round(secs * 1e3, 3),
                    "unit": "ms/batch",
                    "spread_ms": round(spread * 1e3, 3),
                    "per_proof_us": round(secs / n * 1e6, 1),
                }
            )
        )

    if args.snapshot:
        from cpzk_tpu.observability.perf import PerfEntry, write_snapshot

        entries = [
            PerfEntry(
                name=name, backend=backend, n=n,
                value=round(secs * 1e3, 4), unit="ms/batch",
                spread=round(spread * 1e3, 4),
            )
            for name, backend, n, secs, spread in results
        ]
        write_snapshot(
            args.snapshot, entries,
            meta={"bench": "bench_batch", "runs": args.runs},
        )
        print(f"# perf snapshot written to {args.snapshot}", file=sys.stderr)


if __name__ == "__main__":
    main()
