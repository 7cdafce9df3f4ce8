"""verified_per_s: proofs whose verdicts settled inside the window, over
the window's length (whole quanta: the window closes at the first quantum
that settles at or after --seconds)."""


def read(art: dict):
    if "settled" not in art or not art.get("window_s"):
        return None
    return art["settled"] / art["window_s"]
