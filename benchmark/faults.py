"""Faults planted under the timed path, for the tests that show a broken
program reads ``correct: false``.  The benchmark's own runs never install
one.

The audit verifies through ``BatchVerifier.run_prepared`` (under
``DispatchLane.verify_once``, the seam the serving daemon dispatches
through too), so the faults wrap that one seam:

``flip``  the answer altered where it is produced: the first row of every
          batch gets the opposite verdict;
``half``  half of the batch left out: rows past the middle are never
          checked and come back accepted.
"""

from __future__ import annotations

import contextlib

FAULTS = ("flip", "half")


@contextlib.contextmanager
def planted(name: str | None):
    """``name`` planted for the block (nothing when None)."""
    if name is None:
        yield
        return
    from cpzk_tpu.errors import InvalidParams
    from cpzk_tpu.protocol.batch import BatchVerifier

    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}")
    inner = BatchVerifier.run_prepared

    def broken(self, prepared, stages=None):
        results = list(inner(self, prepared, stages))
        if not results:
            return results
        if name == "flip":
            results[0] = (InvalidParams("Proof verification failed")
                          if results[0] is None else None)
        else:
            for i in range(len(results) // 2, len(results)):
                results[i] = None
        return results

    BatchVerifier.run_prepared = broken
    try:
        yield
    finally:
        BatchVerifier.run_prepared = inner
