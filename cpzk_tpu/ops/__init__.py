"""TPU data plane: batched GF(2^255-19) / ristretto255 kernels as JAX programs.

This package is the TPU-native re-design of the compute that the reference
delegates to curve25519-dalek (SURVEY.md §2.2): field arithmetic, point
arithmetic, and batch verification — expressed as vectorized operations over
limb-major ``[NLIMBS, batch]`` int32 arrays — the batch axis rides the
128-wide vector lanes, with `jax.sharding` handling multi-chip scale (see
:mod:`cpzk_tpu.parallel`).

Public surface:

- :mod:`.limbs` / :mod:`.curve` — field + point kernels
- :mod:`.verify` — per-proof and per-row combined verification kernels
- :mod:`.msm` — windowed-Pippenger multi-scalar multiplication
- :mod:`.prove` — fixed-base comb batch proof generation (``BatchProver``)
- :mod:`.backend` — the ``TpuBackend`` dispatching all of the above
- :mod:`.pallas_kernels` — opt-in explicit-tiling kernels (``CPZK_PALLAS=1``)

Submodules import jax lazily enough for host-only use of the package; pull
``TpuBackend``/``BatchProver`` via their submodules to keep import costs
where they are used.
"""
