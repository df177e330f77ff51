"""Window mean of the ResolverRole's overlap counter (`qos.stages.overlap`:
1 for a batch issued while another was still on the device, else 0),
in %. Nothing where the role reports no such counter."""
from harness import window_mean


def read(w):
    if "overlap" not in w.status1["qos"].get("stages", {}):
        return None
    s = window_mean(w, "stages", "overlap")
    return None if s is None else s * 100
