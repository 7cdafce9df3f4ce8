"""audit.wait_us_per_proof.bulk: seconds the audit pipeline's calling
thread spent blocked on a quantum's device phase after preparing the next
quantum (``audit.wait`` spans) over every pass of the audit replay, per
proof settled, in us (spans.py).  Large when the worker's marshal and
device chain paces the replay, near 0 when the host prep does.  A program
that records no ``audit.wait`` span (no overlap) gives None."""

import spans

STAGE = "audit.wait"


def read(art: dict):
    del art
    if not any(s.name == STAGE for t in spans.passes() for s in t.spans):
        return None
    return spans.us_per_proof((STAGE,))
