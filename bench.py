"""Benchmark: batched Chaum-Pedersen proof verification throughput.

Prints ONE JSON line:
    {"metric": "batch_verify_proofs_per_sec", "value": N, "unit": "proofs/s",
     "vs_baseline": R, "plane": P, "kernel": K,
     "device": {"platform": P, "kind": "...", "count": C}}

Baseline: the reference's honest CPU verification rate — ~159 us/proof
(~6289 proofs/s/core) per BASELINE.md; its batch fast path never engages
because of the RLC coefficient bug (SURVEY.md §3.2), so single-proof
verification is the reference's true throughput.

The timed region is the device compute of the corrected-RLC combined batch
check (the accept path for an all-valid batch) — the north-star
configuration of BASELINE.md — via two interchangeable kernels:

- ``rowcombined``: per-row shared-doubling windowed chains + tree sum
  (``ops/verify.combined_kernel``), ~570 point-ops/row, compile-light;
- ``pippenger``: one windowed-Pippenger MSM over all 4N+2 terms
  (``ops/msm``), ~8*K point-adds/row amortized, compile-heavy.

``CPZK_BENCH_KERNEL=auto`` (default) times both, in this one process, and
reports the faster; the rowcombined pass also writes the end-to-end
serving-path rate to ``BENCH_E2E.json``.  Every number names the device it
ran on.  With no TPU the bench fails, unless ``CPZK_BENCH_PLATFORM=cpu``
asks for an XLA-CPU run on purpose (its numbers are not device numbers).

Host-side scalar prep (challenge derivation, alpha draws, digit recode) and
limb marshalling pipeline with device compute in the serving path; they are
measured separately by ``benches/bench_batch.py`` (end-to-end BatchVerifier
timings, batch-vs-individual curves, scaling over N).

Env knobs: CPZK_BENCH_N (default 16384 rows), CPZK_BENCH_ITERS (default 3),
CPZK_BENCH_KERNEL in {auto, rowcombined, pippenger}, CPZK_BENCH_PLATFORM.
"""

from __future__ import annotations

import json
import os
import sys
import time

N = int(os.environ.get("CPZK_BENCH_N", "16384"))
ITERS = int(os.environ.get("CPZK_BENCH_ITERS", "3"))
KERNEL = os.environ.get("CPZK_BENCH_KERNEL", "auto")
CORPUS = 64
BASELINE = 6289.0  # proofs/s, reference single-core CPU (BASELINE.md)
_E2E_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_E2E.json")


def limbs_cols(points):
    """Host edwards points -> [4, 20, n] int32 (X/Y/Z/T limb columns)."""
    import numpy as np

    from cpzk_tpu.ops import limbs

    return np.stack(
        [limbs.ints_to_limbs([p[i] for p in points]) for i in range(4)]
    )


def identity_cols(k):
    """[4, 20, k] identity-point columns via the canonical helper."""
    import numpy as np

    from cpzk_tpu.ops import curve

    return np.stack([np.asarray(c) for c in curve.identity((k,))])


class _Inputs:
    """Corpus proofs tiled to N rows + host-side scalar prep."""

    def __init__(self):
        import numpy as np

        from cpzk_tpu import Parameters, Prover, SecureRng, Transcript, Witness  # noqa: F401
        from cpzk_tpu.core.ristretto import Ristretto255
        from cpzk_tpu.core.scalars import L

        rng = SecureRng()
        self.params = params = Parameters.new()

        # Real proofs, tiled: device group-op cost is data-independent, so
        # tiling does not flatter the numbers, it only keeps host-side
        # corpus generation out of the budget.  Every tiled row still gets
        # its own random alpha.
        from cpzk_tpu.core.transcript import derive_challenges_batch

        proofs = []
        for _ in range(CORPUS):
            prover = Prover(params, Witness(Ristretto255.random_scalar(rng)))
            proofs.append(
                (prover.statement, prover.prove_with_transcript(rng, Transcript()))
            )
        eb = Ristretto255.element_to_bytes
        challenges = derive_challenges_batch(
            [None] * CORPUS,
            [eb(params.generator_g)] * CORPUS,
            [eb(params.generator_h)] * CORPUS,
            [eb(st.y1) for st, _ in proofs],
            [eb(st.y2) for st, _ in proofs],
            [eb(pr.commitment.r1) for _, pr in proofs],
            [eb(pr.commitment.r2) for _, pr in proofs],
        )
        rows = [(st, pr, ch) for (st, pr), ch in zip(proofs, challenges)]
        self.proof_rows = proofs  # (statement, proof) pairs for the e2e pass

        reps = (N + CORPUS - 1) // CORPUS
        self.tile = lambda cols: np.tile(cols, (1, reps))[:, :N]
        self.r1c = limbs_cols([p.commitment.r1.point for _, p, _ in rows])
        self.y1c = limbs_cols([s.y1.point for s, _, _ in rows])
        self.r2c = limbs_cols([p.commitment.r2.point for _, p, _ in rows])
        self.y2c = limbs_cols([s.y2.point for s, _, _ in rows])
        self.gh = limbs_cols([params.generator_g.point, params.generator_h.point])

        self.a = [Ristretto255.random_scalar(rng).value for _ in range(N)]
        self.b = Ristretto255.random_scalar(rng).value
        self.c = [rows[i % CORPUS][2].value for i in range(N)]
        self.s = [rows[i % CORPUS][1].response.s.value for i in range(N)]
        self.ac = [x * y % L for x, y in zip(self.a, self.c)]
        self.ba = [self.b * x % L for x in self.a]
        self.bac = [self.b * x % L for x in self.ac]
        self.sum_as = sum(x * y for x, y in zip(self.a, self.s)) % L
        self.corr = [(L - self.sum_as) % L, (L - self.b * self.sum_as % L) % L]


def _time_kernel(fn, args) -> float:
    import jax

    ok = jax.block_until_ready(fn(*args))  # compile + warmup
    assert bool(ok), "bench batch failed the combined check"
    best = float("inf")
    for _ in range(ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return N / best


def _pippenger_setup(inp: _Inputs):
    """Build device inputs + jitted kernel -> (fn, args); shared by the
    timed bench and the xprof capture (which must set up OUTSIDE its
    trace window)."""
    import numpy as np
    import jax.numpy as jnp

    from cpzk_tpu.ops import msm
    from cpzk_tpu.ops import backend as B

    m_used = 4 * N + 2
    # mirror the production dispatch (ops/backend._combined_pippenger):
    # past LANE_CHUNK the MSM runs as identical per-chunk programs whose
    # partial points tree-sum into one identity test
    c, m_pad = B._msm_shape(N)
    scalars = inp.a + inp.ac + inp.ba + inp.bac + inp.corr
    digits = msm.scalars_to_signed_digits(scalars + [0] * (m_pad - m_used), c)

    ident = identity_cols(m_pad - m_used)
    pts = tuple(
        jnp.asarray(
            np.concatenate(
                [inp.tile(inp.r1c[i]), inp.tile(inp.y1c[i]),
                 inp.tile(inp.r2c[i]), inp.tile(inp.y2c[i]),
                 inp.gh[i], ident[i]],
                axis=1,
            )
        )
        for i in range(4)
    )
    dig = jnp.asarray(digits)
    # the SHARED production dispatch (chunk schedule included): the bench
    # times exactly what TpuBackend serves
    return (lambda p, d: B.chunked_msm_identity(c, p, d)), (pts, dig)


def bench_pippenger(inp: _Inputs) -> float:
    fn, args = _pippenger_setup(inp)
    return _time_kernel(fn, args)


def bench_rowcombined(inp: _Inputs) -> float:
    fn, args = _rowcombined_setup(inp)
    return _time_kernel(fn, args)


def _rowcombined_setup(inp: _Inputs):
    import numpy as np
    import jax.numpy as jnp

    from cpzk_tpu.ops import backend as B

    # correction row is folded in as row N+1 (G with -sum(a s) in the r1
    # slot, H with -b sum(a s) in the y1 slot); identity rows pad to the
    # production lane schedule (ops/backend._pad_lanes): chunked past
    # LANE_CHUNK, mirroring TpuBackend.verify_combined.
    lanes = N + 1
    pad = B._pad_lanes(lanes)
    npad = pad - lanes
    ident = identity_cols(npad)          # post-correction padding rows
    identc = identity_cols(npad + 1)     # identity corr slot + padding

    # build per-slot arrays with the correction column appended
    r1 = tuple(
        jnp.asarray(np.concatenate(
            [inp.tile(inp.r1c[i]), inp.gh[i][:, :1], ident[i]], axis=1))
        for i in range(4)
    )
    y1 = tuple(
        jnp.asarray(np.concatenate(
            [inp.tile(inp.y1c[i]), inp.gh[i][:, 1:2], ident[i]], axis=1))
        for i in range(4)
    )
    r2 = tuple(
        jnp.asarray(np.concatenate(
            [inp.tile(inp.r2c[i]), identc[i]], axis=1))
        for i in range(4)
    )
    y2 = tuple(
        jnp.asarray(np.concatenate(
            [inp.tile(inp.y2c[i]), identc[i]], axis=1))
        for i in range(4)
    )

    from cpzk_tpu.ops.curve import scalars_to_windows

    zeros = [0] * npad
    w_a = jnp.asarray(scalars_to_windows(inp.a + [inp.corr[0]] + zeros))
    w_ac = jnp.asarray(scalars_to_windows(inp.ac + [inp.corr[1]] + zeros))
    w_ba = jnp.asarray(scalars_to_windows(inp.ba + [0] + zeros))
    w_bac = jnp.asarray(scalars_to_windows(inp.bac + [0] + zeros))

    # the SHARED production dispatch (chunk schedule included): the bench
    # times exactly what TpuBackend serves
    def fn(r1_, y1_, r2_, y2_, wa, wac, wba, wbac):
        return B.chunked_combined_identity(
            pad, r1_, y1_, r2_, y2_, wa, wac, wba, wbac)

    return fn, (r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)


def _emit(value: float, device: dict, kernel: str) -> None:
    rec = {
        "metric": "batch_verify_proofs_per_sec",
        "value": round(value, 1),
        "unit": "proofs/s",
        "vs_baseline": round(value / BASELINE, 3),
        "plane": device["platform"],
        "kernel": kernel,
        "device": device,
    }
    print(json.dumps(rec), flush=True)


def _device(platform: str | None) -> dict:
    """The device this process got: a bench that finds no TPU fails,
    unless ``platform`` (CPZK_BENCH_PLATFORM) asked for that one."""
    import jax

    from cpzk_tpu import jaxrt

    jaxrt.enable_compile_cache()
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d)}
    if device["platform"] != (platform or "tpu"):
        sys.exit(f"bench.py: no TPU found (JAX platform {device['platform']!r}); "
                 "set CPZK_BENCH_PLATFORM=cpu for an XLA-CPU run")
    return device


def main() -> None:
    platform = os.environ.get("CPZK_BENCH_PLATFORM")
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    device = _device(platform)
    inp = _Inputs()
    kernels = ("rowcombined", "pippenger") if KERNEL == "auto" else (KERNEL,)
    bench = {"rowcombined": bench_rowcombined, "pippenger": bench_pippenger}
    results = {}
    for kernel in kernels:
        results[kernel] = bench[kernel](inp)
        if kernel == "rowcombined" and (
                KERNEL == "auto" or os.environ.get("CPZK_BENCH_E2E") == "1"):
            _bench_e2e(inp, device)
    best = max(results, key=results.get)
    _emit(results[best], device, best)


def _write_e2e_record(value: float, device: dict) -> None:
    """Overwrite BENCH_E2E.json with ONE record (the latest run)."""
    rec = {
        "metric": "batch_verify_e2e_proofs_per_sec",
        "value": round(value, 1),
        "unit": "proofs/s",
        "vs_baseline": round(value / BASELINE, 3),
        "n": N,
        "platform": device["platform"],
        "device": device,
    }
    # atomic replace: an interrupted run must never leave truncated JSON
    tmp = _E2E_PATH + ".tmp"
    with open(tmp, "w") as f:
        f.write(json.dumps(rec) + "\n")
    os.replace(tmp, _E2E_PATH)


def _bench_e2e(inp: _Inputs, device: dict) -> None:
    """End-to-end serving-path rate (VERDICT r2 item 9): the kernel line
    above times device compute only, while the 6,289/s baseline is a full
    per-proof figure.  This measures challenge derivation (native merlin,
    threaded) + RLC scalar prep + window decomposition + limb marshalling
    + the device combined check for N rows, and OVERWRITES BENCH_E2E.json
    with one JSON line (a second artifact holding the latest run; stdout
    stays one-line)."""
    from cpzk_tpu import BatchVerifier, SecureRng
    from cpzk_tpu.ops.backend import TpuBackend

    from cpzk_tpu.core.ristretto import Ristretto255
    from cpzk_tpu.protocol.batch import BatchEntry

    rng = SecureRng()
    bv = BatchVerifier(backend=TpuBackend(), max_size=N)
    for i in range(N):
        # reuse the corpus proofs without re-validating statements
        st, pr = inp.proof_rows[i % CORPUS]
        bv.entries.append(BatchEntry(inp.params, st, pr, None))

    def once() -> bool:
        rows = bv.prepare_rows(rng)
        beta = Ristretto255.random_scalar(rng)
        return bv.backend.verify_combined(rows, beta)

    if not once():  # warm (device compile already cached by the kernel run)
        raise RuntimeError(
            f"combined batch check rejected an all-valid batch at N={N} "
            "(backend path) — correctness regression, not a timing issue")
    best = float("inf")
    for _ in range(max(1, ITERS - 1)):
        t0 = time.perf_counter()
        ok = once()
        best = min(best, time.perf_counter() - t0)
        if not ok:
            raise RuntimeError("combined check flipped to reject mid-bench")
    _write_e2e_record(N / best, device)


if __name__ == "__main__":
    main()
