"""Window mean per batch of TpuConflictSet's pack stage
(`qos.stages.pack`: the columnar frame packed into kernel arrays), in
ms. Nothing where the role reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    s = window_mean(w, "stages", "pack")
    return None if s is None else s * 1e3
