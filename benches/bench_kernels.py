"""Device-kernel A/B harness (VERDICT r2 items 2-4).

Measures, on whatever backend JAX gives this process (the TPU on the
chip; CPU with --platform cpu), one JSON line per configuration:

- field-mul throughput for each CPZK_MUL variant (schoolbook VPU
  outer-product vs matmul-fold MXU experiment; a Karatsuba level was
  evaluated and removed — int32 headroom, see PROFILE.md §2);
- point add/double throughput (XLA path vs Pallas kernels when enabled);
- Fiat-Shamir challenge derivation (threaded native C++ vs the device
  Keccak pipeline);
- the two batch-verify kernels (rowcombined / pippenger) at small N.

Each config runs in-process; variants toggle module globals, re-tracing
fresh jit graphs.  Timings are best-of-ITERS wall clock around
block_until_ready.

Usage: python benches/bench_kernels.py [--platform cpu] [--n 65536]
       [--iters 5] [--only mul|point|challenge|verify]
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

    for variant in ("schoolbook", "matmulfold"):
        old = limbs.MUL_VARIANT
        limbs.MUL_VARIANT = variant
        try:
            # chain 8 dependent muls so timing isn't dispatch-bound
            def chain(a, b):
                x = limbs.mul(a, b)
                for _ in range(7):
                    x = limbs.mul(x, b)
                return x

            fn = jax.jit(chain)
            dt = best_of(lambda: fn(a, b), iters)
            emit(f"field_mul_{variant}", 8 * n / dt / 1e6, "Mmul/s", n=n)
        except Exception as e:  # a variant failing to lower must not kill the run
            emit(f"field_mul_{variant}", 0.0, "Mmul/s", n=n, error=str(e)[:200])
        finally:
            limbs.MUL_VARIANT = old


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


def bench_challenge(n: int, iters: int) -> None:
    """Fiat-Shamir challenge derivation: threaded native C++ (merlin.cpp)
    vs the device Keccak pipeline (ops/challenge.py) at n rows."""
    import os as _os

    import numpy as np

    from cpzk_tpu.core import _native

    cols = [
        np.frombuffer(_os.urandom(32 * n), dtype=np.uint8).reshape(n, 32).copy()
        for _ in range(7)
    ]
    blobs = [c.tobytes() for c in cols]

    if _native.load() is not None:
        def native_once():
            return _native.challenge_batch([None] * n, *blobs[1:])

        native_once()
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            native_once()
            best = min(best, time.perf_counter() - t0)
        emit("challenge_native_cpp", n / best / 1e3, "kchal/s", n=n)

    try:
        # inside the guard: this import pulls jax, and a jax-less host must
        # still emit the native number above
        from cpzk_tpu.ops.challenge import derive_challenges_device

        def device_once():
            out = derive_challenges_device(None, *cols[1:])
            return out

        device_once()  # compile + warm; output is host numpy (blocking)
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            device_once()
            best = min(best, time.perf_counter() - t0)
        emit("challenge_device", n / best / 1e3, "kchal/s", n=n)

        # fused variant: challenge bytes reduced to scalar limbs ON device
        # (what an all-device challenges->RLC pipeline consumes directly)
        import jax

        from cpzk_tpu.ops import sclimbs

        reduce_fn = jax.jit(sclimbs.reduce_wide)

        def fused_once():
            chal = derive_challenges_device(None, *cols[1:])
            return jax.block_until_ready(
                reduce_fn(sclimbs.bytes_wide_to_limbs(chal))
            )

        fused_once()
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fused_once()
            best = min(best, time.perf_counter() - t0)
        emit("challenge_device_reduced", n / best / 1e3, "kchal/s", n=n)
    except Exception as e:
        emit("challenge_device", 0.0, "kchal/s", n=n, error=str(e)[:200])


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
                    choices=(None, "mul", "point", "verify", "challenge"))
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
    if args.only in (None, "challenge"):
        bench_challenge(args.n, args.iters)
    if args.only in (None, "verify"):
        bench_verify(args.verify_n, args.iters)


if __name__ == "__main__":
    main()
