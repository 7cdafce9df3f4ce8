"""TPU batch proof-generation throughput (BASELINE config 3).

Times BatchProver.prove end-to-end (device comb kernels + host nonces,
challenge derivation, response closing) and the device commitment kernel
alone.  Prints JSON lines.

Usage: python benches/bench_proofgen.py [--n 4096] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--runs", type=int, default=3)
    args = ap.parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from cpzk_tpu import Parameters, SecureRng, jaxrt
    from cpzk_tpu.core.ristretto import Ristretto255
    from cpzk_tpu.ops.prove import BatchProver

    jaxrt.enable_compile_cache()

    rng = SecureRng()
    bp = BatchProver(Parameters.new())
    witnesses = [Ristretto255.random_scalar(rng) for _ in range(args.n)]
    statements = bp.statements(witnesses)  # warms the jit cache too

    # device commitment kernel only
    ks = [Ristretto255.random_scalar(rng).value for _ in range(args.n)]
    bp._fixed_base_bytes(ks)  # warm
    best = float("inf")
    for _ in range(args.runs):
        t0 = time.perf_counter()
        bp._fixed_base_bytes(ks)
        best = min(best, time.perf_counter() - t0)
    print(json.dumps({
        "name": "commitments_device", "n": args.n,
        "value": round(args.n / best, 1), "unit": "proofs/s",
    }))

    # end to end (statements precomputed, as in a serving deployment)
    best = float("inf")
    for _ in range(args.runs):
        t0 = time.perf_counter()
        bp.prove(witnesses, None, rng, statements=statements)
        best = min(best, time.perf_counter() - t0)
    print(json.dumps({
        "name": "batch_prove_e2e", "n": args.n,
        "value": round(args.n / best, 1), "unit": "proofs/s",
    }))


if __name__ == "__main__":
    main()
