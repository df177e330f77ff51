"""Window mean of a sharded conflict set's partition skew
(`qos.stages.shard_skew`: per batch, the most conflict ranges one
partition got over the mean, in %; 100 is even). Nothing where the role
reports no such counter (one chip, or a program without it)."""
from harness import window_mean


def read(w):
    if "shard_skew" not in w.status1["qos"].get("stages", {}):
        return None
    return window_mean(w, "stages", "shard_skew")
