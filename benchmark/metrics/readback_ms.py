"""Window mean per batch of TpuConflictSet's readback stage
(`qos.stages.readback`: the wait for the device, which includes the
tail of the arguments' asynchronous copy, and the copy of the result
columns), in ms. Nothing where the role reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    s = window_mean(w, "stages", "readback")
    return None if s is None else s * 1e3
