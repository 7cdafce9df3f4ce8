"""setup_s: process start to the start of the measured window (boot,
prewarm, registration or log generation, warm-up), by the host clock."""


def read(art: dict):
    return art.get("setup_s")
