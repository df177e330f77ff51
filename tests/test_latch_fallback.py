"""fixpoint_latch contract: trips never externalize wrong verdicts.

The latched tiered kernel (ops/delta.py over ops/group.py,
fixpoint_latch=True) REFUSES a group whose intra-batch conflict chains
run deeper than fixpoint_unroll: GroupVerdict.unconverged trips and the
returned state (both tiers) is the unchanged input state. The host contract (ADVICE r4 + VERDICT r4 task 5):

* TpuConflictSet.resolve_group_args (default check_latch=True) must
  detect the trip and auto-redispatch the SAME args on the exact
  kernel — callers see correct verdicts, never the latched
  garbage.
* prewarm_exact compiles the exact program up front so the fallback is
  a program swap, not an XLA compile stall mid-version-chain (the
  reference resolver never stalls its chain, Resolver.actor.cpp:283-296).

Runs on the CPU lane (conftest pins JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import (
    TpuConflictSet,
    _resolve_tiered_jit,
)
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.utils import packing
from foundationdb_tpu.utils.packing import stack_device_args

pytestmark = pytest.mark.kernel


def chain_batch(config, n, version, snapshot):
    """One batch whose txns form a conflict chain of depth n:
    t0 writes k0; t_i reads k_{i-1} and writes k_i. Sequentially every
    txn commits (each reads the PRE-batch value), but the alternating
    fixpoint needs ~n applications to prove it — deeper than a small
    unroll, so the latch trips."""
    txns = []
    key = lambda i: b"k%04d" % i
    for i in range(n):
        txns.append(
            CommitTransaction(
                read_conflict_ranges=(
                    [] if i == 0 else [(key(i - 1), key(i - 1) + b"\x00")]
                ),
                write_conflict_ranges=[(key(i), key(i) + b"\x00")],
                read_snapshot=snapshot,
            )
        )
    return packing.pack_batch(txns, version, 0, config)


def cfg(**kw):
    d = dict(
        max_key_bytes=8, max_txns=16, max_reads=16, max_writes=16,
        history_capacity=256, window_versions=10_000,
        fixpoint_unroll=1, fixpoint_latch=True,
    )
    d.update(kw)
    return KernelConfig(**d)


def test_latch_trips_and_autoredispatch_matches_exact():
    config = cfg()
    exact = dataclasses.replace(config, fixpoint_latch=False)
    batches = [
        chain_batch(config, 10, version=100, snapshot=50),
        chain_batch(config, 10, version=200, snapshot=150),
    ]
    stacked = stack_device_args(batches)

    # raw latched kernel refuses: unconverged trips, state unchanged
    cs_raw = TpuConflictSet(config)
    before = jax.tree.map(lambda x: np.asarray(x).copy(), cs_raw.state)
    outs_raw = cs_raw.resolve_group_args(stacked, check_latch=False)
    assert bool(np.asarray(outs_raw.unconverged).any())
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(cs_raw.state)):
        np.testing.assert_array_equal(np.asarray(b), a)

    # default path: auto-redispatch serves the exact kernel's decisions
    cs = TpuConflictSet(config)
    outs = cs.resolve_group_args(stacked)
    assert not bool(np.asarray(outs.unconverged).any())

    cs_exact = TpuConflictSet(exact)
    ref = cs_exact.resolve_group_args(stacked)
    np.testing.assert_array_equal(
        np.asarray(outs.verdict), np.asarray(ref.verdict)
    )
    # ... and the post-group history state matches the exact kernel's
    for a, b in zip(jax.tree_util.tree_leaves(cs.state),
                    jax.tree_util.tree_leaves(cs_exact.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prewarm_exact_avoids_fallback_compile():
    config = cfg()
    batches = [chain_batch(config, 10, version=100, snapshot=50)]
    stacked = stack_device_args(batches)

    cs = TpuConflictSet(config)
    cs.prewarm_exact(stacked)
    fn = _resolve_tiered_jit(0, config.fixpoint_unroll, False, 0, False)
    warmed = fn._cache_size()
    assert warmed >= 1

    # the trip + fallback must hit the warmed program, not compile anew
    outs = cs.resolve_group_args(stacked)
    assert not bool(np.asarray(outs.unconverged).any())
    assert fn._cache_size() == warmed


def test_shallow_group_never_trips():
    # unroll=3 covers a depth-2 chain: no trip, no redispatch needed
    config = cfg(fixpoint_unroll=3)
    batches = [chain_batch(config, 3, version=100, snapshot=50)]
    stacked = stack_device_args(batches)
    cs = TpuConflictSet(config)
    outs = cs.resolve_group_args(stacked, check_latch=False)
    assert not bool(np.asarray(outs.unconverged).any())
