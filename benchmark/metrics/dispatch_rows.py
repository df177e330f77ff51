"""Window mean of the rows each batch is dispatched at
(`qos.stages.dispatch_rows`: the read + write rows of the row rung the
conflict set packed the batch at), in rows. Nothing where the role
reports no such counter (a program that pads every batch to the
config's full shape)."""
from harness import window_mean


def read(w):
    if "dispatch_rows" not in w.status1["qos"].get("stages", {}):
        return None
    return window_mean(w, "stages", "dispatch_rows")
