"""TPU serving plane end-to-end: dynamic batching + device verification.

Boots the gRPC auth service with the JAX data plane behind it (TPU when
available, any JAX backend otherwise), registers a population of users,
then fires concurrent logins — the dynamic batcher coalesces them into
device batches while each caller sees ordinary per-RPC semantics.

Run: python examples/tpu_serving.py [--users 12] [--platform cpu]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def main(n_users: int) -> None:
    from cpzk_tpu.client import AuthClient
    from cpzk_tpu.client.__main__ import do_login, do_register
    from cpzk_tpu.ops.backend import TpuBackend
    from cpzk_tpu.protocol.batch import CpuBackend, FailoverBackend
    from cpzk_tpu.server import RateLimiter, ServerState
    from cpzk_tpu.server.batching import DynamicBatcher
    from cpzk_tpu.server.service import serve

    import jax

    print(f"JAX backend: {jax.devices()[0].platform} ({jax.device_count()} device(s))")

    state = ServerState()
    backend = FailoverBackend(TpuBackend(mesh_devices=0), CpuBackend())
    batcher = DynamicBatcher(backend, max_batch=256, window_ms=10.0, pipeline_depth=2)
    server, port = await serve(
        state, RateLimiter(100_000, 100_000), port=0,
        backend=backend, batcher=batcher,
    )
    batcher.start()
    print(f"auth service with TPU data plane on 127.0.0.1:{port}")

    async with AuthClient(f"127.0.0.1:{port}") as client:
        t0 = time.perf_counter()
        for i in range(n_users):
            await do_register(client, f"user{i}", f"pw-{i}")
        print(f"registered {n_users} users in {time.perf_counter() - t0:.2f}s")

        # concurrent logins: the batcher coalesces these into device batches
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *[do_login(client, f"user{i}", f"pw-{i}") for i in range(n_users)]
        )
        dt = time.perf_counter() - t0
        ok = sum("Login OK" in r for r in results)
        print(f"{ok}/{n_users} concurrent logins in {dt:.2f}s "
              f"({n_users / dt:.1f} logins/s incl. Argon2id client KDF)")
        assert ok == n_users

        # a wrong password still fails, through the same batched path
        bad = await do_login(client, "user0", "nope")
        assert "Login OK" not in bad
        print("wrong password rejected (opaque error) — batched semantics intact")

        assert not backend.degraded, "device plane failed over to CPU"
        print("device plane served every verification (no failover)")

    await batcher.stop()
    await server.stop(None)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=12)
    ap.add_argument("--platform", default=None,
                    help="force a jax backend (e.g. cpu)")
    args = ap.parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    asyncio.run(main(args.users))
