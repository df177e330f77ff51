"""Window mean per batch of the ResolverRole's key-sample stage
(`qos.stages.key_sample`: `_note_key_sample` over the batch's begin
keys), in ms. Nothing where the role reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    s = window_mean(w, "stages", "key_sample")
    return None if s is None else s * 1e3
