"""device.idle_share: 1 - the union of the device's op intervals over the
traced window (trace_reduce.py), averaged over the chips used."""


def read(art: dict):
    t = art.get("trace")
    if not t or not t.get("devices") or not t.get("window_s"):
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
