"""Window mean of the bytes the kernel arguments of each batch take on
the device (`qos.stages.device_arg_bytes`), in MB. Nothing where the
role reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    s = window_mean(w, "stages", "device_arg_bytes")
    return None if s is None else s / 1e6
