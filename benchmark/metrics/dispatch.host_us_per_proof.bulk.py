"""dispatch.host_us_per_proof.bulk: host seconds of the dispatch seam
around the device (``pad_and_pack``: challenge derivation and row build;
``marshal``: limb and window packing; ``unpack``: per-row results) over
every pass of the audit replay, per proof settled, in us (spans.py)."""

import spans

STAGES = ("pad_and_pack", "marshal", "unpack")


def read(art: dict):
    del art
    return spans.us_per_proof(STAGES)
