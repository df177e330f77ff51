"""Reply assembly walks only the reports asked for.

`TpuConflictSet._assemble` gives the BatchResult the loop it replaced
gave (kept below as the reference): the same verdicts and the same
conflicting-key reports, in the same order, on batches with history
hits and intra-batch conflicts where no txn, some or every txn asked
for the report, through the object path and the columnar one. It
touches the key of no read row whose txn did not ask.
"""

from __future__ import annotations

import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_base import BatchResult
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.types import CommitTransaction, TransactionResult
from foundationdb_tpu.utils import packing

pytestmark = pytest.mark.kernel

CFG = KernelConfig(max_key_bytes=8, max_txns=64, max_reads=256,
                   max_writes=256, history_capacity=2048,
                   window_versions=100000, delta_capacity=1024)


def assemble_before(batch, verdict, hist_read, intra_first, report,
                    begin_key_of_row) -> BatchResult:
    """The assembly as it was: every read row, then every txn."""
    n = batch.n_txns
    verdicts = [TransactionResult(int(v)) for v in verdict]
    conflicting: dict[int, list[int]] = {}
    hist_hits_by_txn: dict[int, list[tuple[bytes, int]]] = {}
    for r in range(batch.n_reads):
        if hist_read[r]:
            t = int(batch.read_txn[r])
            idx = int(batch.read_index[r])
            hist_hits_by_txn.setdefault(t, []).append(
                (begin_key_of_row(r), idx)
            )
    for t in range(n):
        if not report[t]:
            continue
        if verdicts[t] != TransactionResult.CONFLICT:
            continue
        if t in hist_hits_by_txn:
            hits = sorted(hist_hits_by_txn[t])
            conflicting[t] = [i for _, i in hits]
        elif intra_first[t] >= 0:
            conflicting[t] = [int(intra_first[t])]
    return BatchResult(verdicts=verdicts, conflicting_key_ranges=conflicting)


def stream(seed: int, ask: float):
    """Three batches of up to four reads a txn over 12 keys: the later
    ones read what the earlier ones wrote (history hits, several a txn,
    begin keys out of read order) and each other (intra-batch
    conflicts); `ask` of the txns ask for the report."""
    rng = np.random.default_rng(seed)
    keys = [b"k%02d" % i for i in range(12)]
    out = []
    for b in range(3):
        version = 1000 * (b + 2)
        txns = [CommitTransaction(
            read_conflict_ranges=[(k, k + b"\0") for k in
                                  rng.choice(keys, rng.integers(0, 5))],
            write_conflict_ranges=[(k, k + b"\0") for k in
                                   rng.choice(keys, rng.integers(0, 3))],
            read_snapshot=version - int(rng.integers(1, 1500)),
            report_conflicting_keys=bool(rng.random() < ask),
        ) for _ in range(48)]
        out.append((version, txns))
    return out


@pytest.mark.parametrize("path", ["object", "columnar"])
@pytest.mark.parametrize("ask", [0.0, 0.5, 1.0], ids=["none", "some", "all"])
def test_assembly_matches_the_loop_it_replaced(path, ask):
    cs = TpuConflictSet(CFG)
    hits = reports = 0
    for version, txns in stream(7, ask):
        if path == "object":
            pending = cs.issue(txns, version)
        else:
            cols = packing.pack_columnar(txns)
            pending = cs.issue_columnar_packed(
                cols, cs.pack_columnar_batch(cols, version))
        cs.read_back(pending)
        verdict, _o, hist_read, intra_first, _i = pending.host
        n = pending.batch.n_txns
        touched = []

        def key_of(r):
            touched.append(r)
            return pending.begin_key_of_row(r)

        want = assemble_before(pending.batch, verdict[:n], hist_read,
                               intra_first[:n], pending.report,
                               pending.begin_key_of_row)
        got = TpuConflictSet._assemble(pending.batch, verdict[:n], hist_read,
                                       intra_first[:n], pending.report,
                                       key_of)
        assert got.verdicts == want.verdicts
        assert all(type(v) is TransactionResult for v in got.verdicts)
        assert (list(got.conflicting_key_ranges.items())
                == list(want.conflicting_key_ranges.items()))
        asked = np.asarray(pending.report, bool)
        read_txn = pending.batch.read_txn[:pending.batch.n_reads]
        assert all(asked[read_txn[r]] and hist_read[r] for r in touched)
        hits += int(hist_read[:pending.batch.n_reads].sum())
        reports += sum(len(v) > 1 for v in want.conflicting_key_ranges.values())
        assert cs.complete(pending).verdicts == want.verdicts
    assert hits > 0
    if ask:
        assert reports > 0  # some txn reports several history hits
