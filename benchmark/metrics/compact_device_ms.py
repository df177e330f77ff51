"""Device time per resolve batch of the compaction program (`compact`,
or `_shard_compact` on a mesh: it folds the delta tier into the main
one every compact_interval batches), from the window's trace, per chip,
in ms. Nothing without a trace or where no program of that name ran."""
import re

COMPACT = re.compile(r"^jit_(_shard_)?compact(\(|$)")


def read(w):
    t = w.trace
    if t is None or not w.batches:
        return None
    s = sum(sec for name, sec in t["programs_top"] if COMPACT.match(name))
    return s / w.batches * 1e3 if s else None
