"""Group kernel parity: resolve_group(G batches) == the reference.

The group kernel (ops/group.py) must be decision-identical to resolving
the same batches one after another in the Python oracle
(testing/oracle.ConflictOracle, the reference's ConflictBatch semantics)
— including the hard part: a read's snapshot can land BETWEEN the
group's commit versions, so its conflicts with earlier in-group batches
are version-dependent, exactly as if those batches had already merged
into history.

Per batch, the verdicts, the decision counts and the conflicting-key
report (assembled from the kernel's per-read history hits and first
intra-batch hit, as the served path assembles it) must equal the
oracle's. The final history STATE must be semantically identical too:
both maps are evaluated at every boundary either holds, where a read
could still conflict (boundary arrays may differ in redundant rows).
"""

from __future__ import annotations

import bisect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.ops import group as G
from foundationdb_tpu.ops import history as H
from foundationdb_tpu.testing.oracle import ConflictOracle, OracleTxn
from foundationdb_tpu.utils import packing

from conftest import random_key, random_range

# compile-heavy kernel tests: run with -m kernel (fast lane: -m 'not kernel')
pytestmark = pytest.mark.kernel


def small_config(**kw):
    defaults = dict(
        max_key_bytes=8,
        max_txns=16,
        max_reads=32,
        max_writes=32,
        history_capacity=512,
        window_versions=1000,
    )
    defaults.update(kw)
    return KernelConfig(**defaults)


def random_txn(rng, *, n_ranges=2, snap_lo, snap_hi, blind_prob=0.15):
    reads = [] if rng.random() < blind_prob else [
        random_range(rng) for _ in range(1 + int(rng.integers(0, n_ranges)))
    ]
    writes = [random_range(rng) for _ in range(1 + int(rng.integers(0, n_ranges)))]
    return CommitTransaction(
        read_conflict_ranges=reads,
        write_conflict_ranges=writes,
        read_snapshot=int(rng.integers(snap_lo, snap_hi)),
    )


def gen_group(rng, g, base_version=1000, step=100, n_txns=12):
    """G (txns, version) batches whose snapshots deliberately straddle
    the group's commit versions (the cross-batch visibility trap)."""
    stream = []
    for i in range(g):
        version = base_version + (i + 1) * step
        txns = [
            random_txn(
                rng,
                snap_lo=max(0, base_version - 2 * step),
                snap_hi=version,  # exclusive: snap < own commit version
            )
            for _ in range(n_txns)
        ]
        stream.append((txns, version))
    return stream


def pack(config, stream):
    return [packing.pack_batch(t, v, 0, config) for t, v in stream]


def canonical_map(state, config):
    """(boundary bytes, version) pairs with redundant rows collapsed."""
    mk = np.asarray(state.main_keys)
    mv = np.asarray(state.main_ver)
    rows = []
    for j in range(mk.shape[0]):
        if all(x == 0xFFFFFFFF for x in mk[j]):
            continue
        rows.append((tuple(mk[j]), int(mv[j])))
    rows.sort()
    # collapse equal-key rows (keep last = value in force) and
    # value-repeats (redundant boundaries)
    dedup = {}
    for k, v in rows:
        dedup[k] = v  # later rows (same key) overwrite: sorted order keeps last
    out = []
    for k in sorted(dedup):
        if not out or out[-1][1] != dedup[k]:
            out.append((k, dedup[k]))
    return out


def live_maps(state, oracle, config):
    """The device history and the oracle's, each evaluated at every
    boundary either holds: the version in force there, or None where it
    is at or below the oracle's MVCC floor (no read that is not too old
    can conflict with it there; unwritten keys read 0 in the oracle and
    VERSION_NEG on the device)."""
    dev = canonical_map(state, config)
    ref = [
        (tuple(packing.pack_key(b, config.max_key_bytes).tolist()), v)
        for b, v in zip(oracle.history.boundaries, oracle.history.values)
    ]

    def at(rows, key, background):
        i = bisect.bisect_right([k for k, _ in rows], key) - 1
        v = rows[i][1] if i >= 0 else background
        return v if v > oracle.oldest else None

    points = sorted({k for k, _ in dev} | {k for k, _ in ref})
    return (
        [at(dev, p, H.VERSION_NEG) for p in points],
        [at(ref, p, oracle.history.background) for p in points],
    )


def oracle_txns(txns):
    return [
        OracleTxn(t.read_conflict_ranges, t.write_conflict_ranges,
                  t.read_snapshot, True)
        for t in txns
    ]


def assert_batch_matches(out, i, batch, txns, want, label=""):
    """Row i of a GroupVerdict against the oracle's result for the
    same batch: verdicts, counts, and the conflicting-key report."""
    n = len(txns)
    verdict = np.asarray(out.verdict[i])[:n]
    assert verdict.tolist() == want.verdicts, f"{label}verdict batch {i}"
    assert int(out.committed_count[i]) == want.verdicts.count(G.COMMITTED)
    assert int(out.too_old_count[i]) == want.verdicts.count(G.TOO_OLD)
    report = TpuConflictSet._assemble(
        batch, verdict, np.asarray(out.hist_conflict_read[i]),
        np.asarray(out.intra_first_range[i])[:n], [True] * n,
        lambda r: txns[int(batch.read_txn[r])].read_conflict_ranges[
            int(batch.read_index[r])][0],
    )
    assert report.conflicting_key_ranges == want.conflicting_ranges, (
        f"{label}conflicting-key report batch {i}"
    )


def run_group(config, batches, state=None):
    state = H.init(config) if state is None else state
    stacked = packing.stack_device_args(batches)
    state, out = jax.jit(G.resolve_group)(state, stacked)
    return state, jax.tree_util.tree_map(np.asarray, out)


def assert_group_matches(config, stream, oracle=None, state=None):
    """Resolve `stream` as one group (on `state`, else empty history)
    and compare batch for batch with `oracle` (else a fresh one)."""
    oracle = oracle or ConflictOracle(config.window_versions)
    batches = pack(config, stream)
    s_grp, grp_out = run_group(config, batches, state)
    for i, (txns, version) in enumerate(stream):
        want = oracle.resolve(oracle_txns(txns), version)
        assert_batch_matches(grp_out, i, batches[i], txns, want)
    got, ref = live_maps(s_grp, oracle, config)
    assert got == ref, "final history maps diverge"
    return s_grp, grp_out


@pytest.mark.parametrize("seed", range(8))
def test_group_matches_sequential_random(seed):
    rng = np.random.default_rng(seed)
    assert_group_matches(small_config(), gen_group(rng, g=4))


def test_group_snapshot_straddles_versions():
    """A read whose snapshot >= an earlier group batch's version must NOT
    conflict with that batch's writes (it already saw them)."""
    config = small_config()
    k = lambda i: bytes([i])
    t_writer = CommitTransaction(
        read_conflict_ranges=[],
        write_conflict_ranges=[(k(5), k(6))],
        read_snapshot=50,
    )
    # snapshot 150 >= batch-0 version 100: writer already visible
    t_reader_new = CommitTransaction(
        read_conflict_ranges=[(k(5), k(6))],
        write_conflict_ranges=[(k(9), k(10))],
        read_snapshot=150,
    )
    # snapshot 90 < 100: conflict
    t_reader_old = CommitTransaction(
        read_conflict_ranges=[(k(5), k(6))],
        write_conflict_ranges=[(k(11), k(12))],
        read_snapshot=90,
    )
    _, out = assert_group_matches(
        config, [([t_writer], 100), ([t_reader_new, t_reader_old], 200)]
    )
    assert out.verdict[1][0] == G.COMMITTED  # saw the write already
    assert out.verdict[1][1] == G.CONFLICT   # stale snapshot


def test_group_too_old_and_blind_writes():
    config = small_config(window_versions=100)
    k = lambda i: bytes([i])
    stale = CommitTransaction(
        read_conflict_ranges=[(k(1), k(2))],
        write_conflict_ranges=[(k(1), k(2))],
        read_snapshot=5,
    )
    blind = CommitTransaction(
        read_conflict_ranges=[],
        write_conflict_ranges=[(k(3), k(4))],
        read_snapshot=5,  # stale snapshot but NO reads: never too old
    )
    _, out = assert_group_matches(
        config, [([stale, blind], 200), ([stale], 300)]
    )
    assert out.verdict[0][0] == G.TOO_OLD
    assert out.verdict[0][1] == G.COMMITTED


@pytest.mark.parametrize("seed", range(4))
def test_group_hot_key_contention(seed):
    """Zipf-style: every batch reads+writes one hot range — long
    cross-batch conflict chains exercise the fixpoint depth."""
    rng = np.random.default_rng(100 + seed)
    config = small_config()
    hot = (b"\x10", b"\x11")
    stream = []
    base, step = 1000, 100
    for i in range(4):
        version = base + (i + 1) * step
        txns = []
        for _t in range(8):
            txns.append(CommitTransaction(
                read_conflict_ranges=[hot] if rng.random() < 0.7 else [random_range(rng)],
                write_conflict_ranges=[hot] if rng.random() < 0.7 else [random_range(rng)],
                read_snapshot=int(rng.integers(base - step, version)),
            ))
        stream.append((txns, version))
    assert_group_matches(config, stream)


def test_group_continuation_across_groups():
    """State threads between groups: group 2 must see group 1's writes
    as ordinary history."""
    rng = np.random.default_rng(7)
    config = small_config()
    stream = gen_group(rng, g=6, n_txns=10)
    oracle = ConflictOracle(config.window_versions)
    state, _ = assert_group_matches(config, stream[:3], oracle)
    assert_group_matches(config, stream[3:], oracle, state)


@pytest.mark.parametrize("seed", range(4))
def test_group_parity_with_prestate(seed):
    """Parity — including the per-read conflicting-key report — when
    history is NON-empty before the group (a txn condemned by pre-group
    history must still report its cross-batch conflicting reads)."""
    rng = np.random.default_rng(200 + seed)
    config = small_config()
    pre = gen_group(rng, g=2, base_version=500)
    stream = gen_group(rng, g=4, base_version=1000)
    oracle = ConflictOracle(config.window_versions)
    for txns, version in pre:
        oracle.resolve(oracle_txns(txns), version)
    state, _ = run_group(config, pack(config, pre))
    assert_group_matches(config, stream, oracle, state)


@pytest.mark.parametrize("seed", range(4))
def test_short_span_path_matches_general(seed):
    """short_span_limit=S compiles the direct range ops; on workloads
    within the span bound it must be decision-identical to the general
    path, with no latch trip."""
    import functools

    rng = np.random.default_rng(300 + seed)
    config = small_config()
    # point-ish ranges: single-byte keys, [k, k+1) style
    def point_txn():
        k = bytes([int(rng.integers(0, 40))])
        k2 = bytes([int(rng.integers(0, 40))])
        return CommitTransaction(
            read_conflict_ranges=[(k, k + b"\x01")],
            write_conflict_ranges=[(k2, k2 + b"\x01")],
            read_snapshot=int(rng.integers(900, 1100 + 100 * rng.integers(1, 3))),
        )

    batches = [
        packing.pack_batch(
            [point_txn() for _ in range(10)], 1000 + (i + 1) * 100, 0, config
        )
        for i in range(3)
    ]
    stacked = packing.stack_device_args(batches)

    s0, out0 = jax.jit(G.resolve_group)(H.init(config), stacked)
    jf = jax.jit(functools.partial(G.resolve_group, short_span_limit=8))
    s1, out1 = jf(H.init(config), stacked)
    np.testing.assert_array_equal(
        np.asarray(out1.verdict), np.asarray(out0.verdict)
    )
    np.testing.assert_array_equal(
        np.asarray(out1.hist_conflict_read), np.asarray(out0.hist_conflict_read)
    )
    assert not bool(np.asarray(out1.overflow).any()), "latch must not trip"
    assert canonical_map(s1, config) == canonical_map(s0, config)


def test_short_span_latch_trips_on_wide_ranges():
    """A range wider than the limit must trip the loud latch (overflow),
    never silently resolve."""
    import functools

    config = small_config()
    wide = CommitTransaction(
        read_conflict_ranges=[(b"\x00", b"\x30")],  # spans many keys
        write_conflict_ranges=[
            (bytes([i]), bytes([i]) + b"\x01") for i in range(12)
        ],
        read_snapshot=1000,
    )
    b0 = packing.pack_batch([wide], 1100, 0, config)
    jf = jax.jit(functools.partial(G.resolve_group, short_span_limit=2))
    _s, out = jf(H.init(config), packing.stack_device_args([b0]))
    assert bool(np.asarray(out.overflow).any())


def test_group_of_one_equals_resolve_batch():
    """G=1, the shape the delta tier runs the group kernel at."""
    rng = np.random.default_rng(3)
    assert_group_matches(small_config(), gen_group(rng, g=1))


def test_fixpoint_latch_refuses_deep_chains_and_preserves_state():
    """fixpoint_latch mode: convergence is checked, not assumed. A
    conflict chain deeper than the unroll trips GroupVerdict.unconverged
    and the state comes back UNCHANGED; with enough unroll the decisions
    are identical to the exact while-loop kernel."""
    import jax
    import functools

    import numpy as np

    from foundationdb_tpu.config import TEST_CONFIG
    from foundationdb_tpu.models.types import CommitTransaction
    from foundationdb_tpu.ops import group as G
    from foundationdb_tpu.ops import history as H
    from foundationdb_tpu.utils import packing

    # a LONG alternating chain: txn i reads key[i-1] and writes key[i]
    # with distinct keys -> committed/conflicted alternates, chain depth
    # ~B (the worst case for a bounded unroll)
    n = 12
    txns = []
    for i in range(n):
        k_prev = b"ch%02d" % (i - 1) if i else b"zz"
        k = b"ch%02d" % i
        txns.append(CommitTransaction(
            read_conflict_ranges=[(k_prev, k_prev + b"\x00")],
            write_conflict_ranges=[(k, k + b"\x00")],
            read_snapshot=5,
        ))
    batch = packing.pack_batch(txns, 10, 0, TEST_CONFIG)
    stacked = packing.stack_device_args([batch])

    def run(latch, unroll):
        state = H.init(TEST_CONFIG)
        fn = jax.jit(functools.partial(
            G.resolve_group, fixpoint_unroll=unroll, fixpoint_latch=latch
        ))
        st2, out = fn(state, stacked)
        return state, st2, out

    # exact kernel: ground truth
    _, st_exact, out_exact = run(latch=False, unroll=2)
    assert not bool(np.asarray(out_exact.unconverged).any())

    # latch kernel, too-shallow unroll: refuses, state unchanged
    st0, st_l, out_l = run(latch=True, unroll=2)
    assert bool(np.asarray(out_l.unconverged).all())
    assert (np.asarray(st_l.main_ver) == np.asarray(st0.main_ver)).all()
    assert (np.asarray(st_l.main_keys) == np.asarray(st0.main_keys)).all()

    # latch kernel, enough unroll: identical decisions + merge
    _, st_ok, out_ok = run(latch=True, unroll=n + 2)
    assert not bool(np.asarray(out_ok.unconverged).any())
    assert (
        np.asarray(out_ok.verdict) == np.asarray(out_exact.verdict)
    ).all()
    assert (
        np.asarray(st_ok.main_ver) == np.asarray(st_exact.main_ver)
    ).all()
