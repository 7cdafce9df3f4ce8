"""audit.host_us_per_proof.bulk: host seconds of the audit pipeline's own
stages (log open and scan, record decode, proof parse, the outcome fold,
the cursor checkpoint with its fsync, the signed report) over every pass,
per proof settled, in us (spans.py)."""

import spans

STAGES = ("audit.open", "audit.decode", "audit.parse", "audit.fold",
          "audit.checkpoint", "audit.report")


def read(art: dict):
    del art
    return spans.us_per_proof(STAGES)
