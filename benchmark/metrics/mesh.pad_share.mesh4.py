"""mesh.pad_share.mesh4: identity pad lanes over all lanes the mesh
programs took (the program's ``mesh.lanes{kind=term|pad}`` counter, over
the run): the combined check's 4n+2 terms padded to the slice MSM's
lanes, and the per-row fallback's rows padded to a mesh multiple.  A
program that counts no mesh lanes gives None."""


def read(art: dict):
    del art
    try:
        from cpzk_tpu.server import metrics
    except ImportError:
        return None
    pad = metrics.read("mesh.lanes", labels={"kind": "pad"})
    term = metrics.read("mesh.lanes", labels={"kind": "term"})
    return pad / (pad + term) if pad + term else None
