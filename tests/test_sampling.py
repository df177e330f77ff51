"""cluster/sampling unit pins: the keyspace-skew sensing substrate.

The byte sample must be a PURE FUNCTION of (seed, key, size) — the
soak determinism pin (`--status-probe`) rides on that — its range
queries must be unbiased against exact byte counts, the tag counter
must decay and roll over deterministically under the virtual clock,
and the attribution rule must hold in BOTH directions (dominant flags,
flat stays quiet, starved range samples never flag).
"""

import random

import numpy as np
import pytest

from foundationdb_tpu.cluster.sampling import (
    DOMINANCE_FRAC,
    HOT_RANGE_MIN_KEYS,
    KEY_SAMPLE_LIMIT,
    KEY_SAMPLE_NUMPY_MIN_KEYS,
    ByteSample,
    TagCounter,
    attribute_hotspot,
    decay_key_sample,
    key_sample_qos,
    note_begin_keys,
    note_columnar_begin_keys,
    tag_of_key,
)


def _kv_stream(seed, n=4000, value_bytes=512):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        t = rng.randrange(8)
        k = f"tenant{t}/k{rng.randrange(500):05d}".encode()
        out.append((k, b"v" * value_bytes))
    return out


# ---------------------------------------------------------------------------
# ByteSample


def test_byte_sample_deterministic_and_order_independent():
    kvs = _kv_stream(1)
    a = ByteSample(seed=42)
    b = ByteSample(seed=42)
    for k, v in kvs:
        a.note_write(k, v)
    shuffled = list(kvs)
    random.Random(9).shuffle(shuffled)
    for k, v in shuffled:
        b.note_write(k, v)
    # same seed, same final (key, size) set -> bit-identical sample,
    # regardless of arrival order (keys repeat; last size wins — the
    # shuffle preserves per-key last-write order only by luck, so
    # compare on a dedup'd stream)
    dedup = {}
    for k, v in kvs:
        dedup[k] = v
    a2, b2 = ByteSample(seed=42), ByteSample(seed=42)
    items = list(dedup.items())
    for k, v in items:
        a2.note_write(k, v)
    random.Random(9).shuffle(items)
    for k, v in items:
        b2.note_write(k, v)
    assert a2.items() == b2.items()
    assert a2.total_bytes() == b2.total_bytes()
    # ...and a different seed draws a different sample
    c = ByteSample(seed=43)
    for k, v in dedup.items():
        c.note_write(k, v)
    assert c.items() != a2.items()


def test_sampled_bytes_range_accuracy_vs_exact():
    kvs = {}
    for k, v in _kv_stream(2, n=6000):
        kvs[k] = v
    bs = ByteSample(seed=7)
    for k, v in kvs.items():
        bs.note_write(k, v)
    exact_total = sum(len(k) + len(v) for k, v in kvs.items())
    est_total = bs.sampled_bytes()
    assert est_total == bs.total_bytes()
    # the weight sum is an unbiased estimator; at ~500 sampled keys the
    # relative error on the full range sits comfortably inside 15%
    assert abs(est_total - exact_total) / exact_total < 0.15
    # per-prefix range query (half-open [begin, end)) vs exact
    for t in ("tenant0", "tenant3", "tenant7"):
        begin = f"{t}/".encode()
        end = f"{t}0".encode()  # '0' > '/' — covers the whole prefix
        exact = sum(
            len(k) + len(v) for k, v in kvs.items()
            if begin <= k < end
        )
        est = bs.sampled_bytes(begin, end)
        assert abs(est - exact) / exact < 0.4
    # end=None reaches +inf (keys above any finite end still count)
    assert bs.sampled_bytes(b"tenant4/") == sum(
        len(k) + len(v) for k, v in kvs.items() if k >= b"tenant4/"
    ) or bs.sampled_bytes(b"tenant4/") > 0


def test_erase_and_erase_range():
    bs = ByteSample(seed=3, factor=1, overhead=0)
    for i in range(32):
        bs.note_write(b"e/%02d" % i, b"v" * 64)
    assert bs.count == 32  # factor=1: p >= 1, everything samples
    bs.erase(b"e/05")
    assert bs.count == 31
    bs.erase(b"e/05")  # idempotent
    assert bs.count == 31
    bs.erase_range(b"e/10", b"e/20")
    assert bs.count == 21
    assert bs.sampled_bytes(b"e/10", b"e/20") == 0


def test_overwrite_resamples_at_new_size():
    bs = ByteSample(seed=5, factor=1, overhead=0)
    bs.note_write(b"ow/key", b"v" * 100)
    assert bs.total_bytes() == 106
    bs.note_write(b"ow/key", b"v" * 10)  # shrink: old entry replaced
    assert bs.count == 1
    assert bs.total_bytes() == 16


def test_gc_halves_scale_and_stays_unbiased():
    bs = ByteSample(seed=11, factor=1, overhead=0, capacity=64)
    for i in range(256):
        bs.note_write(b"gc/%04d" % i, b"v" * 64)
    assert bs.gc_rounds >= 1
    assert bs.count <= 64
    assert bs.scale < 1.0
    # survivors' weights are scaled up so the estimator stays unbiased:
    # true bytes = 256 * (7 + 64) = 18176
    exact = 256 * (7 + 64)
    assert abs(bs.total_bytes() - exact) / exact < 0.5


def test_snapshot_restore_round_trip():
    bs = ByteSample(seed=13, factor=10, overhead=4, capacity=128)
    for i in range(400):
        bs.note_write(b"snap/%04d" % i, b"v" * 200)
    snap = bs.snapshot()
    other = ByteSample(seed=0)  # knobs must come FROM the snapshot
    other.restore(snap)
    assert other.seed == bs.seed
    assert other.factor == 10 and other.overhead == 4
    assert other.capacity == 128
    assert other.scale == bs.scale
    assert other.items() == bs.items()
    assert other.total_bytes() == bs.total_bytes()
    assert other.hot_ranges() == bs.hot_ranges()


def test_hot_ranges_rows_carry_key_support():
    bs = ByteSample(seed=17, factor=1, overhead=0)
    for i in range(20):
        bs.note_write(b"tenant0/k%02d" % i, b"v" * 64)
    for i in range(2):
        bs.note_write(b"tenant1/k%02d" % i, b"v" * 64)
    rows = bs.hot_ranges()
    assert rows[0]["range"] == "tenant0"
    assert rows[0]["keys"] == 20
    assert rows[0]["frac"] > 0.8
    assert rows[1] == {
        "range": "tenant1", "begin": "tenant1/k00", "end": "tenant1/k01",
        "bytes": rows[1]["bytes"], "keys": 2, "frac": rows[1]["frac"],
    }


# ---------------------------------------------------------------------------
# TagCounter


def test_tag_counter_decay_under_virtual_clock():
    t = [0.0]
    tc = TagCounter(folding_time=1.0, clock=lambda: t[0])
    for _ in range(10):
        tc.note("hot", 1000)
        t[0] += 0.1
    busy = tc.busiest()
    assert busy["tag"] == "hot"
    rate_now = busy["bytes_per_s"]
    assert rate_now > 0
    t[0] += 10.0  # ten folding times of silence
    assert tc.busiest()["bytes_per_s"] < rate_now / 100
    assert tc.bytes_noted == 10000  # the ledger counter never decays
    assert tc.notes == 10


def test_tag_counter_rollover_evicts_cold_half():
    t = [0.0]
    tc = TagCounter(capacity=4, folding_time=1.0, clock=lambda: t[0])
    for i in range(4):
        tc.note(f"cold{i}", 10)
    t[0] += 5.0  # cold tags decay
    tc.note("hot", 10000)  # 5th tag -> rollover first
    assert tc.rollovers == 1
    assert len(tc._rates) <= 3  # half of 4 evicted, then hot added
    assert tc.busiest()["tag"] == "hot"


def test_tag_counter_untagged_counts_toward_total_only():
    t = [0.0]
    tc = TagCounter(folding_time=1.0, clock=lambda: t[0])
    tc.note(None, 500)
    t[0] += 0.5
    tc.note("a", 500)
    t[0] += 0.5
    rows = tc.top()
    assert [r["tag"] for r in rows] == ["a"]
    assert rows[0]["frac"] < 0.9  # untagged bytes dilute the fraction


# ---------------------------------------------------------------------------
# tag derivation + key-sample helpers


def test_tag_of_key():
    assert tag_of_key(b"tenant3/k001") == "tenant3"
    assert tag_of_key(b"\x1etenant3/k001") == "tenant3"  # tenant prefix
    assert tag_of_key(b"noslashkey") is None
    assert tag_of_key(b"/leading") is None
    assert tag_of_key(b"x" * 40 + b"/k") is None  # prefix too long
    assert tag_of_key(b"a/b/c") == "a"  # first separator wins


def test_decay_key_sample_and_qos():
    sample = {b"a": 8, b"b": 3, b"c": 1}
    decay_key_sample(sample)
    assert sample == {b"a": 4, b"b": 1}  # zeros dropped
    wide = {b"k%04d" % i: 2 for i in range(100)}
    decay_key_sample(wide, limit=10)
    assert len(wide) == 5  # heaviest half of the limit kept
    qos = key_sample_qos({b"x/1": 5, b"x/2": 2}, top_n=1)
    assert qos == {"keys": 2, "top": [{"key": "x/1", "count": 5}]}


# ---------------------------------------------------------------------------
# columnar begin-key counting: numpy path against the loop


def _loop_reference(sample, cols, limit=KEY_SAMPLE_LIMIT):
    """The resolver role's key-sample loop over a columnar frame, as it
    stood before the numpy path: the reference the numpy path must
    match in keys, counts and insertion order."""
    if len(cols.key_lens) == 0:
        return
    offs = np.concatenate(([0], np.cumsum(cols.key_lens, dtype=np.int64)))
    blob = bytes(cols.key_blob)
    nr, nw = cols.n_reads, cols.n_writes
    for i in (*range(nr), *range(2 * nr, 2 * nr + nw)):
        b = blob[offs[i]:offs[i + 1]]
        sample[b] = sample.get(b, 0) + 1
    if len(sample) > limit:
        decay_key_sample(sample, limit)


def _frame(reads, writes=()):
    """A columnar frame of point-range txns: txn i reads reads[i] (if
    any) and writes writes[i] (if any)."""
    from foundationdb_tpu.models.types import CommitTransaction
    from foundationdb_tpu.utils.packing import pack_columnar

    n = max(len(reads), len(writes))
    return pack_columnar([
        CommitTransaction(
            read_conflict_ranges=(
                [(reads[i], reads[i] + b"\0")] if i < len(reads) else []
            ),
            write_conflict_ranges=(
                [(writes[i], writes[i] + b"\0")] if i < len(writes) else []
            ),
        )
        for i in range(n)
    ])


def _words8(rng, n, hi=20_000_000):
    return [int(v).to_bytes(8, "big")
            for v in rng.integers(0, hi, n, dtype=np.uint64)]


def _case_uniform_8_byte(rng):
    # the resolver cell's shape: 64K begin keys over 20M ids, a decay
    # on every batch
    return [], [(_frame(_words8(rng, 32768), _words8(rng, 32768)), True)
                for _ in range(3)]


def _case_hot_keys(rng):
    # a few hot keys repeated within and across batches, over a cold
    # uniform tail; the decay runs on every batch
    hot = _words8(rng, 40)
    out = []
    for _ in range(4):
        keys = [hot[int(i)] for i in rng.zipf(1.5, 6000) % len(hot)]
        keys += _words8(rng, 6000)
        rng.shuffle(keys)
        out.append((_frame(keys[:6000], keys[6000:]), True))
    return [], out


def _case_no_decay(rng):
    # repeats from a small pool: the sample stays under the limit, so
    # every new key is kept in first-appearance order
    pool = _words8(rng, 1500)
    return [], [
        (_frame([pool[int(i)] for i in rng.integers(0, 1500, 2500)]), True)
        for _ in range(3)
    ]


def _case_truncation_ties(rng):
    # 10K keys seen twice each: after halving all tie at 1, more than
    # the limit, so the heaviest half keeps dict order; old keys carry
    # ties of their own across batches
    out = []
    for _ in range(3):
        keys = _words8(rng, 10000) * 2
        rng.shuffle(keys)
        out.append((_frame(keys[:10000], keys[10000:]), True))
    return [], out


def _case_mixed_short(rng):
    # mixed lengths 0-7 with repeats: the length rides in the word
    pool = [bytes(rng.integers(0, 256, int(n), dtype=np.uint8))
            for n in rng.integers(0, 8, 3000)]
    out = []
    for _ in range(3):
        keys = [pool[int(i)] for i in rng.integers(0, 3000, 5000)]
        out.append((_frame(keys[:2500], keys[2500:]), True))
    return [], out


def _case_fixed_short(rng):
    # every begin key 4 bytes: zero-padded words
    keys = [k[:4] for k in _words8(rng, 3000, hi=2**64 - 1)] * 2
    return [], [(_frame(keys[:3000], keys[3000:]), True)]


def _case_long_keys_fall_back(rng):
    keys = [b"%012d" % int(v) for v in rng.integers(0, 10**12, 4096)]
    return [], [(_frame(keys[:2048], keys[2048:]), False)]


def _case_mixed_8_9_fall_back(rng):
    keys = _words8(rng, 4095) + [b"123456789"]
    return [], [(_frame(keys[:2048], keys[2048:]), False)]


def _case_empty_and_reads_only(rng):
    return [b"k1", b"k2"], [
        (_frame([]), False),
        (_frame(_words8(rng, 3000)), True),
        (_frame([]), False),
    ]


def _case_small_batch_keeps_the_loop(rng):
    users = [b"user%d" % i for i in range(4)]
    return [], [(_frame(users[:2], users[2:]), False)]


def _case_crossover(rng):
    # one key under the crossover keeps the loop; at it, numpy
    n = KEY_SAMPLE_NUMPY_MIN_KEYS
    return [], [(_frame(_words8(rng, n - 1)), False),
                (_frame(_words8(rng, n)), True)]


def _case_union_at_the_limit(rng):
    # the union reaches the limit exactly (no decay), then passes it by
    # one key (decay)
    prior = [b"user%d" % i for i in range(KEY_SAMPLE_LIMIT - 2048)]
    first = _words8(rng, 2048, hi=2**64 - 1)
    second = first[1:] + _words8(rng, 1, hi=2**64 - 1)
    return prior, [(_frame(first), True), (_frame(second), True)]


def _case_sample_from_object_frames(rng):
    # keys of other lengths noted earlier (object frames), some 8-byte
    # keys among them reappearing in the columnar batch
    shared = _words8(rng, 300)
    prior = [b"user%d" % i for i in range(200)] + [b"longer-key-%d" % i
                                                    for i in range(200)]
    prior += shared + shared[:100]
    keys = shared * 3 + _words8(rng, 3000)
    rng.shuffle(keys)
    wider = shared + _words8(rng, 5000)  # the second batch decays
    return prior, [(_frame(keys[:2000], keys[2000:]), True),
                   (_frame(wider), True)]


COLUMNAR_CASES = {
    name[len("_case_"):]: fn
    for name, fn in sorted(globals().items())
    if name.startswith("_case_")
}


@pytest.mark.parametrize("case", sorted(COLUMNAR_CASES))
def test_columnar_begin_keys_match_the_loop(case):
    rng = np.random.default_rng(28)
    prior, batches = COLUMNAR_CASES[case](rng)
    ref, got = {}, {}
    note_begin_keys(ref, prior)
    note_begin_keys(got, prior)
    for cols, fast in batches:
        _loop_reference(ref, cols)
        assert note_columnar_begin_keys(got, cols) is fast
        assert list(got.items()) == list(ref.items())


# ---------------------------------------------------------------------------
# attribution


def _status(tags=None, ranges=None):
    return {"cluster": {
        "busiest_tags": tags or [], "hot_ranges": ranges or [],
    }}


def test_attribute_dominant_tag():
    attr = attribute_hotspot(_status(
        tags=[{"tag": "tenant0", "bytes_per_s": 9e4, "frac": 0.7}],
    ))
    assert attr["attributed"]
    assert attr["hot_tag"]["tag"] == "tenant0"
    assert attr["hot_range"] is None
    assert attr["threshold"] == DOMINANCE_FRAC


def test_attribute_flat_mix_stays_quiet():
    attr = attribute_hotspot(_status(
        tags=[{"tag": f"t{i}", "bytes_per_s": 10.0, "frac": 0.125}
              for i in range(8)],
        ranges=[{"range": f"t{i}", "bytes": 100, "keys": 20,
                 "frac": 0.125} for i in range(8)],
    ))
    assert not attr["attributed"]


def test_attribute_hot_range_requires_key_support():
    # a 2-key sample putting half its weight in one range is noise —
    # the HOT_RANGE_MIN_KEYS floor must hold the verdict back...
    starved = attribute_hotspot(_status(
        ranges=[{"range": "tenant0", "bytes": 5000,
                 "keys": HOT_RANGE_MIN_KEYS - 1, "frac": 0.6}],
    ))
    assert not starved["attributed"]
    # ...and release it once the sample actually supports the fraction
    supported = attribute_hotspot(_status(
        ranges=[{"range": "tenant0", "bytes": 5000,
                 "keys": HOT_RANGE_MIN_KEYS, "frac": 0.6}],
    ))
    assert supported["attributed"]
    assert supported["hot_range"]["range"] == "tenant0"


def test_attribute_custom_threshold():
    st = _status(tags=[{"tag": "a", "bytes_per_s": 1.0, "frac": 0.4}])
    assert not attribute_hotspot(st)["attributed"]
    assert attribute_hotspot(st, threshold=0.3)["attributed"]


# ---------------------------------------------------------------------------
# the drill plan (testing/hotspot): seeded, direction-salted


def test_plan_workload_deterministic_and_skewed():
    from foundationdb_tpu.testing.hotspot import DEFAULTS, plan_workload

    cfg = dict(DEFAULTS)
    a = plan_workload(3, True, cfg)
    b = plan_workload(3, True, cfg)
    assert a == b
    assert plan_workload(4, True, cfg) != a
    uni = plan_workload(3, False, cfg)
    assert uni != a

    def frac0(keys):
        return sum(k.startswith(b"tenant0/") for k in keys) / len(keys)

    assert frac0(a) > DOMINANCE_FRAC  # zipf(2.0): top tenant dominates
    assert frac0(uni) < 0.3


@pytest.mark.slow
def test_hotspot_sim_gate_both_directions():
    from foundationdb_tpu.testing.hotspot import run_hotspot_sim

    zipf = run_hotspot_sim(seed=1, skewed=True, quick=True)
    assert zipf["ok"], zipf["why"]
    assert zipf["attribution"]["attributed"]
    flat = run_hotspot_sim(seed=1, skewed=False, quick=True)
    assert flat["ok"], flat["why"]
    assert not flat["attribution"]["attributed"]
