"""Window mean of the host bytes of the kernel arguments each batch hands
the device (`qos.stages.arg_bytes`), in MB. Nothing where the role
reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    s = window_mean(w, "stages", "arg_bytes")
    return None if s is None else s / 1e6
