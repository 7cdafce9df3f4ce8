"""The one traffic generator: every mix is a data file under
``benchmark/traffic/<mix>.json`` that names its ``kind`` and parameters.

Everything here is drawn from ``--seed`` alone; the program under test
only ever sees the generated log.  Every seed gets the same sizes (record,
reject and lie counts), at other indices, so seeds change the draw and
not the amount of work.

Kinds:

``proof_log``
    ``records``, ``statements`` (distinct keypairs, record i proves for
    statement i mod statements), ``reject_frac`` (exact share proved with
    a wrong secret; logged verdict 0), ``lie_frac`` (exact share whose
    logged verdict is flipped).
"""

from __future__ import annotations

import json
import os
import random

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    """The mix ``benchmark/traffic/<name>.json``."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {name}: unknown kind {mix.get('kind')!r}")
    return mix


class SeededBytes:
    """``SecureRng``'s surface (``fill_bytes``) over a seeded stream: keys
    and nonces from the seed (test data, not a CSPRNG).  Copied from
    chip_smoke.py's ``Rng`` (PR 21)."""

    def __init__(self, seed: int):
        self._r = random.Random(seed)

    def fill_bytes(self, n: int) -> bytes:
        return self._r.randbytes(n)


def marked(mix: dict, seed: int) -> tuple[set, set]:
    """The indices proved with a wrong secret and those logged with a lying
    verdict: exact counts, at seeded places."""
    pick = random.Random(seed + 1)
    n = int(mix["records"])
    wrong = set(pick.sample(range(n), round(float(mix["reject_frac"]) * n)))
    lie = set(pick.sample(range(n), round(float(mix["lie_frac"]) * n)))
    return wrong, lie


def proof_log(mix: dict, seed: int):
    """(records, wrong, lie): the proof-log payloads (the program's
    ``proof_record`` shape) in log order, and the sets of indices proved
    with a wrong secret and logged with a lying verdict.  A seeded copy of
    ``python -m cpzk_tpu.audit generate`` (cpzk_tpu/audit/__main__.py
    ``cmd_generate``), with wrong-secret rejects at seeded indices in
    place of its every-k-th corrupted scalar."""
    from cpzk_tpu import Parameters, Prover, Transcript, Witness
    from cpzk_tpu.core.ristretto import Ristretto255

    rng = SeededBytes(seed)
    n = int(mix["records"])
    k = int(mix["statements"])
    params = Parameters.new()
    provers = [Prover(params, Witness(Ristretto255.random_scalar(rng)))
               for _ in range(k)]
    eb = Ristretto255.element_to_bytes
    y1 = [eb(p.statement.y1).hex() for p in provers]
    y2 = [eb(p.statement.y2).hex() for p in provers]
    wrong, lie = marked(mix, seed)
    records = []
    for i in range(n):
        ctx = rng.fill_bytes(32)
        t = Transcript()
        t.append_context(ctx)
        prover = provers[(i + 1) % k] if i in wrong else provers[i % k]
        wire = prover.prove_with_transcript(rng, t).to_bytes()
        records.append({
            "u": f"u{i % k}", "y1": y1[i % k], "y2": y2[i % k],
            "ctx": ctx.hex(), "p": wire.hex(),
            "v": int((i not in wrong) != (i in lie)), "t": 0,
        })
    return records, wrong, lie


KINDS = {"proof_log"}
