"""Device-kernel A/B harness (VERDICT r2 items 2-4).

Measures, on whatever backend JAX gives this process (the TPU on the
chip; CPU with --platform cpu), one JSON line per configuration:

- field-mul throughput (the schoolbook VPU outer product);
- point add/double throughput (XLA path vs Pallas kernels when enabled);
- the two batch-verify kernels (rowcombined / pippenger) at small N.

Each config runs in-process.  Timings are best-of-ITERS wall clock
around block_until_ready.

Usage: python benches/bench_kernels.py [--platform cpu] [--n 65536]
       [--iters 5] [--only mul|point|verify]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def best_of(fn, iters: int) -> float:
    import jax

    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def emit(name: str, value: float, unit: str, **extra) -> None:
    print(json.dumps({"name": name, "value": round(value, 1), "unit": unit, **extra}), flush=True)


def bench_mul(n: int, iters: int) -> None:
    import secrets

    import jax

    from cpzk_tpu.ops import limbs

    xs = [secrets.randbelow(limbs.P) for _ in range(256)]
    ys = [secrets.randbelow(limbs.P) for _ in range(256)]
    import numpy as np

    reps = (n + 255) // 256
    a = jax.device_put(np.tile(limbs.ints_to_limbs(xs), (1, reps))[:, :n])
    b = jax.device_put(np.tile(limbs.ints_to_limbs(ys), (1, reps))[:, :n])

    # chain 8 dependent muls so timing isn't dispatch-bound
    def chain(a, b):
        x = limbs.mul(a, b)
        for _ in range(7):
            x = limbs.mul(x, b)
        return x

    try:
        fn = jax.jit(chain)
        dt = best_of(lambda: fn(a, b), iters)
        emit("field_mul_schoolbook", 8 * n / dt / 1e6, "Mmul/s", n=n)
    except Exception as e:  # a failure to lower must not kill the run
        emit("field_mul_schoolbook", 0.0, "Mmul/s", n=n, error=str(e)[:200])


def _random_points(n: int):
    import numpy as np

    from cpzk_tpu.core import edwards
    from cpzk_tpu.ops import curve

    base = [edwards.pt_scalar_mul(edwards.BASEPOINT, i + 2) for i in range(64)]
    reps = (n + 63) // 64
    cols = curve.points_to_device(base)
    import jax

    return tuple(jax.device_put(np.tile(np.asarray(c), (1, reps))[:, :n]) for c in cols)


def bench_point(n: int, iters: int) -> None:
    import jax

    from cpzk_tpu.ops import curve

    P = _random_points(n)

    def chain_add(p):
        x = curve.add(p, p)
        for _ in range(7):
            x = curve.add(x, p)
        return x

    def chain_dbl(p):
        x = curve.double(p)
        for _ in range(7):
            x = curve.double(x)
        return x

    from cpzk_tpu.ops import pallas_kernels

    for name, f in (("point_add", chain_add), ("point_double", chain_dbl)):
        try:
            fn = jax.jit(f)
            dt = best_of(lambda: fn(P), iters)
            emit(name, 8 * n / dt / 1e6, "Mop/s", n=n,
                 pallas=pallas_kernels.enabled())
        except Exception as e:  # a config failing to lower must not kill the run
            emit(name, 0.0, "Mop/s", n=n, pallas=pallas_kernels.enabled(),
                 error=str(e)[:200])


def bench_verify(n: int, iters: int) -> None:
    """rowcombined + pippenger end-to-end device timings at modest N —
    the same kernels bench.py guards, but runnable inline for tuning."""
    os.environ.setdefault("CPZK_BENCH_ITERS", str(iters))
    os.environ["CPZK_BENCH_N"] = str(n)
    import importlib

    import bench as bench_mod

    importlib.reload(bench_mod)
    inp = bench_mod._Inputs()
    for kernel, fn in (
        ("rowcombined", bench_mod.bench_rowcombined),
        ("pippenger", bench_mod.bench_pippenger),
    ):
        try:
            rate = fn(inp)
            emit(f"verify_{kernel}", rate, "proofs/s", n=n,
                 vs_baseline=round(rate / bench_mod.BASELINE, 3))
        except Exception as e:
            emit(f"verify_{kernel}", 0.0, "proofs/s", n=n, error=str(e)[:200])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--verify-n", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--only", default=None,
                    choices=(None, "mul", "point", "verify"))
    args = ap.parse_args()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    import jax

    from cpzk_tpu import jaxrt

    jaxrt.enable_compile_cache()
    emit("backend", len(jax.devices()), "devices",
         kind=jax.devices()[0].platform)

    if args.only in (None, "mul"):
        bench_mul(args.n, args.iters)
    if args.only in (None, "point"):
        bench_point(args.n, args.iters)
    if args.only in (None, "verify"):
        bench_verify(args.verify_n, args.iters)


if __name__ == "__main__":
    main()
