"""mesh.host_us_per_proof.mesh4: host seconds the mesh adds to a quantum's
dispatch (``mesh.digits``: the combined check's scalar products and their
signed-digit recode) over every pass of the audit replay, per proof
settled, in us (spans.py).  A program that records no ``mesh.digits``
span gives None."""

import spans

STAGES = ("mesh.digits",)


def read(art: dict):
    del art
    if not any(s.name in STAGES for t in spans.passes() for s in t.spans):
        return None
    return spans.us_per_proof(STAGES)
