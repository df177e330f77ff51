"""Window mean per batch of the ResolverRole's wire codec stages: the
resolve request's frame decode plus its reply's encode
(`qos.stages.decode` and `.encode`), in ms. Nothing where the role
reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    dec = window_mean(w, "stages", "decode")
    enc = window_mean(w, "stages", "encode")
    return None if dec is None or enc is None else (dec + enc) * 1e3
