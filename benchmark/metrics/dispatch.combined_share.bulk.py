"""dispatch.combined_share.bulk: the share of multi-row batches that ran
the combined RLC check, over the run (the program's
``batch.combined{outcome=accepted|rejected|skipped}`` counter: accepted
plus rejected over all three).  A program that counts none gives None."""

OUTCOMES = ("accepted", "rejected", "skipped")


def read(art: dict):
    del art
    try:
        from cpzk_tpu.server import metrics
    except ImportError:
        return None
    n = {o: metrics.read("batch.combined", labels={"outcome": o})
         for o in OUTCOMES}
    total = sum(n.values())
    return (n["accepted"] + n["rejected"]) / total if total else None
