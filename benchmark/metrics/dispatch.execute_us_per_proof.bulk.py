"""dispatch.execute_us_per_proof.bulk: seconds of the dispatch seam's
``execute`` spans (program launch, the device's run and the blocking
result fetch: the host waiting on the device) over every pass of the
audit replay, per proof settled, in us (spans.py)."""

import spans


def read(art: dict):
    del art
    return spans.us_per_proof(("execute",))
