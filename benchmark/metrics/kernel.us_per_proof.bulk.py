"""kernel.us_per_proof.bulk: device seconds of the verify programs in the
traced window (the combined check, its partials and the per-row
verify_each, matched by module name) per proof settled in it (``traced``),
in us."""

KERNELS = ("combined", "each", "partials", "msm")


def read(art: dict):
    t = art.get("trace")
    if not t or not t.get("devices") or not art.get("traced"):
        return None
    device_s = sum(v for k, v in t["programs"].items()
                   if any(m in k for m in KERNELS))
    return 1e6 * device_s / art["traced"] if device_s else None
