"""Capture one JAX profiler (xprof) trace of a batch-verify kernel.

VERDICT r4 item 1's last sub-goal ("one xprof trace"): runs the chosen
kernel at N rows — compile untraced, then ITERS timed executions inside
``jax.profiler.trace`` — so the trace holds steady-state device steps,
not compilation.  Inspect with ``tensorboard --logdir <outdir>``.

Usage: python benches/capture_xprof.py [--n 4096] [--kernel rowcombined]
       [--outdir .hw/xprof] [--platform cpu]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--kernel", default="rowcombined",
                    choices=("rowcombined", "pippenger"))
    ap.add_argument("--outdir", default=".hw/xprof")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    os.environ["CPZK_BENCH_N"] = str(args.n)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    # the persistent compile cache bench.py uses: a rerun must not pay
    # the kernel compile twice
    from cpzk_tpu import jaxrt

    jaxrt.enable_compile_cache()

    import time

    import bench as bench_mod

    inp = bench_mod._Inputs()
    setup = {"rowcombined": bench_mod._rowcombined_setup,
             "pippenger": bench_mod._pippenger_setup}[args.kernel]
    # inputs, jit wrapper, compile and warmup all OUTSIDE the trace
    # window: the trace must hold only steady-state device executions
    fn, kargs = setup(inp)

    import jax

    ok = jax.block_until_ready(fn(*kargs))
    if not bool(ok):
        raise SystemExit("combined check rejected the warmup batch — "
                         "refusing to trace a broken run")

    best = float("inf")
    with jax.profiler.trace(args.outdir):
        with jax.profiler.TraceAnnotation(f"cpzk_{args.kernel}_{args.n}"):
            for _ in range(args.iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*kargs))
                best = min(best, time.perf_counter() - t0)
    print(f"traced {args.kernel} at N={args.n}: {args.n / best:.1f} "
          f"proofs/s -> {args.outdir}")


if __name__ == "__main__":
    main()
