"""Window mean per batch of TpuConflictSet's assemble stage
(`qos.stages.assemble`: the reply built from the result columns), in
ms. Nothing where the role reports no stages."""
from harness import window_mean


def read(w):
    if "stages" not in w.status1["qos"]:
        return None
    s = window_mean(w, "stages", "assemble")
    return None if s is None else s * 1e3
