"""Window mean of the intra-batch fixpoint applications each batch ran
(`qos.stages.fixpoint_iters`: the kernel's unrolled applications plus
its residual loop's iterations), in applications. Nothing where the
role reports no such counter (a program that does not count them)."""
from harness import window_mean


def read(w):
    if "fixpoint_iters" not in w.status1["qos"].get("stages", {}):
        return None
    return window_mean(w, "stages", "fixpoint_iters")
