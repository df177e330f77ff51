"""Window mean per batch of TpuConflictSet's transfer stage
(`qos.stages.transfer`), in ms: the host time to issue the kernel
arguments' `jax.device_put`. The copy itself completes asynchronously
and the device waits for it, so a smaller or faster copy shows in
`readback_ms`, not here. Nothing where the role reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    s = window_mean(w, "stages", "transfer")
    return None if s is None else s * 1e3
