"""audit.gc_us_per_proof.bulk: seconds the cyclic collector ran, on any
thread, while the audit replay's passes were open (``audit.gc`` spans,
one per collection) over every pass, per proof settled, in us
(spans.py).  A program that counts its collections registers the
``audit.gc.collections`` counter at each run's start, and a pass in which
none ran reads 0; a program that does not (no such counter) gives
None."""

import spans

STAGE = "audit.gc"
COUNTER = ("c", "audit.gc.collections")


def read(art: dict):
    del art
    try:
        from cpzk_tpu.server import metrics
    except ImportError:
        return None
    if COUNTER not in metrics.registered():
        return None
    return spans.us_per_proof((STAGE,))
