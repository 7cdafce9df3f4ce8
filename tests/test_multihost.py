"""Real 2-process ``jax.distributed`` job on the CPU backend (VERDICT r2
item 6): two subprocesses form a coordinator-backed job, build the global
batch mesh, and run one sharded verify over it — covering the main path of
:mod:`cpzk_tpu.parallel.multihost` (``jax.distributed.initialize``, global
device view, cross-process ``shard_map``) that the single-process no-op
test cannot reach.

Each process contributes 2 virtual CPU devices (XLA_FLAGS), so the global
mesh is 4 devices across 2 OS processes — the same topology class as two
TPU hosts on DCN, minus the physical ICI.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
import jax

jax.config.update("jax_platforms", "cpu")  # before any device use

from cpzk_tpu.parallel import multihost

multihost.initialize()  # CPZK_COORDINATOR / _NUM_PROCESSES / _PROCESS_ID env

EXPECT_PC = int(os.environ["CPZK_TEST_EXPECT_PROCS"])
EXPECT_LOCAL = int(os.environ["CPZK_TEST_EXPECT_LOCAL"])

pi, pc = multihost.process_info()
assert pc == EXPECT_PC, f"expected {EXPECT_PC} processes, got {pc}"
assert jax.device_count() == EXPECT_PC * EXPECT_LOCAL, jax.device_count()
assert len(jax.local_devices()) == EXPECT_LOCAL

mesh = multihost.global_batch_mesh()
assert mesh.devices.size == EXPECT_PC * EXPECT_LOCAL

# Deterministic corpus: every process must build identical host data (SPMD
# over identical replicated inputs).  A counter-stream "rng" replaces the
# OS entropy source.
import hashlib


class StubRng:
    def __init__(self, seed: bytes):
        self.seed, self.n = seed, 0

    def fill_bytes(self, k: int) -> bytes:
        out = b""
        while len(out) < k:
            out += hashlib.sha256(self.seed + self.n.to_bytes(8, "little")).digest()
            self.n += 1
        return out[:k]


from cpzk_tpu import Parameters, Prover, Transcript, Witness
from cpzk_tpu.core.ristretto import Ristretto255
from cpzk_tpu.protocol.batch import BatchRow, BatchVerifier
from cpzk_tpu.ops.backend import TpuBackend

rng = StubRng(b"multihost-test")
params = Parameters.new()
rows = []
for i in range(6):
    pr = Prover(params, Witness(Ristretto255.random_scalar(rng)))
    proof = pr.prove_with_transcript(rng, Transcript())
    rows.append((pr.statement, proof))

backend = TpuBackend(mesh_devices=0)  # global mesh: all devices
assert backend._mesh is not None
assert backend._mesh.devices.size == EXPECT_PC * EXPECT_LOCAL

# all-valid batch: the combined RLC single-check path must accept it
# across the cross-process mesh (TpuBackend.prefers_combined)
bv = BatchVerifier(backend=backend)
for st, p in rows:
    bv.add(params, st, p)
assert bv.verify(rng) == [None] * 6

# mismatched row -> combined check fails -> per-row fallback isolates it
bv = BatchVerifier(backend=backend)
for st, p in rows:
    bv.add(params, st, p)
bv.add(params, rows[0][0], rows[1][1])  # mismatched row -> index 6 fails
res = bv.verify(rng)
flags = [r is None for r in res]
assert flags == [True] * 6 + [False], flags

print(f"MULTIHOST_OK process={pi}/{pc} devices={jax.device_count()}")
"""


def test_single_process_global_mesh_serves_backend_and_prover():
    """Default-suite multihost coverage (VERDICT r4 item 5): the same
    entrypoints a pod deployment uses — ``multihost.initialize`` (no-op
    single-process), ``global_batch_mesh`` — feed a TpuBackend verify and
    a BatchProver statement pass over the full 8-virtual-device mesh, so
    the multihost module is exercised beyond import without the slow
    2-process gate."""
    from cpzk_tpu import Parameters, Prover, SecureRng, Transcript, Witness
    from cpzk_tpu.core.ristretto import Ristretto255
    from cpzk_tpu.ops.backend import TpuBackend
    from cpzk_tpu.ops.prove import BatchProver
    from cpzk_tpu.parallel import multihost
    from cpzk_tpu.protocol.batch import BatchVerifier

    multihost.initialize()  # unconfigured: must be a no-op, not a latch
    pi, pc = multihost.process_info()
    assert (pi, pc) == (0, 1)
    mesh = multihost.global_batch_mesh()
    import jax

    assert mesh.devices.size == jax.device_count() >= 1

    rng = SecureRng()
    params = Parameters.new()
    bv = BatchVerifier(backend=TpuBackend(mesh_devices=0))
    witnesses = [Ristretto255.random_scalar(rng) for _ in range(3)]
    for w in witnesses:
        prover = Prover(params, Witness(w))
        t = Transcript()
        t.append_context(b"mh")
        proof = prover.prove_with_transcript(rng, t)
        bv.add_with_context(params, prover.statement, proof, b"mh")
    assert bv.verify(rng) == [None] * 3

    # prover side over the same global mesh: device statements must match
    # the host-plane derivation bit-exactly
    bp = BatchProver(params, mesh_devices=0)
    for (y1b, y2b), w in zip(bp.statements(witnesses), witnesses):
        g, h = params.generator_g, params.generator_h
        assert y1b == Ristretto255.element_to_bytes(Ristretto255.scalar_mul(g, w))
        assert y2b == Ristretto255.element_to_bytes(Ristretto255.scalar_mul(h, w))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
@pytest.mark.skipif(
    not os.environ.get("CPZK_SLOW_TESTS"),
    reason="set CPZK_SLOW_TESTS=1 (CI slow tier) — spawns a coordinator-"
    "backed multi-process job, ~2 min each",
)
@pytest.mark.parametrize(
    "n_procs,local_devices",
    [
        (2, 2),  # two hosts x two chips: the v5e-slice topology class
        (4, 1),  # four hosts x one chip: max process fan-out on DCN
    ],
)
def test_multi_process_distributed_sharded_verify(n_procs, local_devices):
    port = _free_port()
    env_base = dict(os.environ)
    env_base.pop("JAX_PLATFORMS", None)
    env_base["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={local_devices}"
    )
    env_base["CPZK_COORDINATOR"] = f"127.0.0.1:{port}"
    env_base["CPZK_NUM_PROCESSES"] = str(n_procs)
    env_base["CPZK_TEST_EXPECT_PROCS"] = str(n_procs)
    env_base["CPZK_TEST_EXPECT_LOCAL"] = str(local_devices)
    env_base["CPZK_NO_NATIVE_BUILD"] = "1"  # no concurrent make churn

    procs = []
    for pid in range(n_procs):
        env = dict(env_base, CPZK_PROCESS_ID=str(pid))
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER],
                env=env,
                cwd=REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=600)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("distributed workers timed out")

    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:\n{out}\nstderr:\n{err[-3000:]}"
        assert "MULTIHOST_OK" in out, out
