"""Knobs: typed runtime constants with randomize-under-test, plus BUGGIFY.

Behavioral mirror of the reference's knob system (`flow/Knobs.cpp`,
`fdbclient/ServerKnobs.cpp`): every tunable is a named, typed constant;
under simulation a seeded fraction of knobs take randomized values to
widen coverage (the `randomize && BUGGIFY` idiom, e.g.
ServerKnobs.cpp:43-44), and `buggify(...)` deterministically enables rare
code paths per seed (flow/include/flow/flow.h:63-81 BUGGIFY).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import numpy as np


@dataclasses.dataclass
class _KnobDef:
    name: str
    default: Any
    ktype: type
    randomize: Optional[Callable[[np.random.Generator], Any]] = None


class Knobs:
    """A named knob collection (FLOW_KNOBS / SERVER_KNOBS shape)."""

    def __init__(self, name: str):
        object.__setattr__(self, "_name", name)
        object.__setattr__(self, "_defs", {})
        object.__setattr__(self, "_values", {})

    def define(self, name: str, default, *, randomize=None) -> None:
        d = _KnobDef(name, default, type(default), randomize)
        self._defs[name] = d
        self._values[name] = default

    def __getattr__(self, name: str):
        try:
            return object.__getattribute__(self, "_values")[name]
        except KeyError:
            raise AttributeError(f"unknown knob {name!r}") from None

    def __setattr__(self, name: str, value) -> None:
        self.set(name, value)

    def set(self, name: str, value) -> None:
        """--knob_<name>=<value> (type-checked against the default)."""
        if name not in self._defs:
            raise KeyError(f"unknown knob {name!r}")
        d = self._defs[name]
        if not isinstance(value, d.ktype):
            value = d.ktype(value)
        self._values[name] = value

    def reset(self) -> None:
        for n, d in self._defs.items():
            self._values[n] = d.default

    def randomize_under_test(self, rng: np.random.Generator, prob: float = 0.5):
        """Seeded knob randomization (ServerKnobs' randomize && BUGGIFY)."""
        chosen = {}
        for n, d in self._defs.items():
            if d.randomize is not None and rng.random() < prob:
                self._values[n] = chosen[n] = d.randomize(rng)
        return chosen

    def as_dict(self) -> dict:
        return dict(self._values)

    def apply_env_overrides(self, env_var: str = None) -> dict:
        """Apply `NAME=value;NAME=value` overrides from an environment
        variable (default FDBTPU_KNOB_OVERRIDES) — the hook the
        autotuner's subprocess harnesses use to drive knob trials
        (scripts/autotune.py sets it per trial; values are coerced via
        set()'s type check). Returns {name: value} of what was applied
        so harnesses can record the knob fingerprint honestly."""
        import os as _os

        raw = _os.environ.get(env_var or "FDBTPU_KNOB_OVERRIDES", "")
        applied = {}
        for part in raw.split(";"):
            part = part.strip()
            if not part:
                continue
            name, _, value = part.partition("=")
            name, value = name.strip(), value.strip()
            d = self._defs.get(name)
            if d is not None and d.ktype is bool:
                # bool('False') is True — env strings need real
                # parsing, and an unrecognized spelling is a config
                # error, never a silent True
                lowered = value.lower()
                if lowered in ("1", "true", "yes", "on"):
                    parsed = True
                elif lowered in ("0", "false", "no", "off"):
                    parsed = False
                else:
                    raise ValueError(
                        f"knob {name!r}: {value!r} is not a boolean "
                        "(use true/false/1/0)"
                    )
                self.set(name, parsed)
            else:
                self.set(name, value)
            applied[name] = self._values[name]
        return applied


class Buggifier:
    """Deterministic rare-branch activation (BUGGIFY).

    Each call site (identified by its string tag) is enabled once per
    seed with `activation_prob`; enabled sites then fire with
    `fire_prob` per evaluation — the reference's two-level scheme
    (flow/flow.h:63-81: P_ENABLED per site, P_FIRE per hit).
    """

    def __init__(self, seed: int = 0, *, enabled: bool = False,
                 activation_prob: float = 0.25, fire_prob: float = 0.05):
        self.enabled = enabled
        self.activation_prob = activation_prob
        self.fire_prob = fire_prob
        self._rng = np.random.default_rng(seed)
        self._site_enabled: dict[str, bool] = {}

    def __call__(self, site: str) -> bool:
        if not self.enabled:
            return False
        if site not in self._site_enabled:
            self._site_enabled[site] = (
                float(self._rng.random()) < self.activation_prob
            )
        return self._site_enabled[site] and (
            float(self._rng.random()) < self.fire_prob
        )


#: Global buggifier — off outside simulation, like the reference's.
BUGGIFY = Buggifier()


def make_server_knobs() -> Knobs:
    """The resolver-relevant server knobs with reference defaults
    (fdbclient/ServerKnobs.cpp:36-44, 549-550 + resolver/commit knobs)."""
    k = Knobs("ServerKnobs")
    k.define("VERSIONS_PER_SECOND", 1_000_000)
    k.define(
        "MAX_READ_TRANSACTION_LIFE_VERSIONS",
        5_000_000,
        randomize=lambda r: int(
            r.choice([1_000_000, 2_000_000, 5_000_000])
        ),
    )
    k.define(
        "MAX_WRITE_TRANSACTION_LIFE_VERSIONS",
        5_000_000,
        randomize=lambda r: int(
            r.choice([1_000_000, 2_000_000, 5_000_000])
        ),
    )
    k.define("RESOLVER_STATE_MEMORY_LIMIT", 1_000_000)
    k.define(
        "COMMIT_TRANSACTION_BATCH_INTERVAL_MIN", 0.001,
        randomize=lambda r: float(r.choice([0.001, 0.005, 0.01])),
    )
    # Adaptive commit batching (the reference's dynamic commitBatcher,
    # fdbserver/CommitProxyServer.actor.cpp:361 + ServerKnobs
    # COMMIT_TRANSACTION_BATCH_*): the interval SHRINKS when batches
    # fill early (load) and relaxes when dispatches go out underfull;
    # batch count/bytes targets follow the measured resolve+log stage
    # latency. All movement is bounded by these knobs.
    k.define(
        "COMMIT_TRANSACTION_BATCH_INTERVAL_MAX", 0.020,
        randomize=lambda r: float(r.choice([0.010, 0.020, 0.050])),
    )
    k.define("COMMIT_TRANSACTION_BATCH_INTERVAL_SMOOTHER_ALPHA", 0.1)
    # the interval tracks this fraction of the smoothed resolve+log
    # stage latency (the reference's BATCH_INTERVAL_LATENCY_FRACTION):
    # slow stages earn longer windows (bigger batches amortize a fixed
    # per-dispatch cost), fast pipelines shrink back toward MIN
    k.define("COMMIT_TRANSACTION_BATCH_INTERVAL_LATENCY_FRACTION", 0.1)
    k.define("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 32768)
    k.define("COMMIT_TRANSACTION_BATCH_BYTES_MAX", 8 << 20)
    # per-batch resolve+log stage-latency budget the count/bytes targets
    # steer toward (seconds): latency above budget shrinks the targets,
    # latency under half budget with full batches grows them
    k.define("COMMIT_BATCH_STAGE_LATENCY_BUDGET", 0.100)
    # GRV batching follows the same controller (GrvProxyServer's
    # START_TRANSACTION_BATCH_* discipline)
    k.define("START_TRANSACTION_BATCH_INTERVAL_MIN", 0.0005)
    k.define(
        "START_TRANSACTION_BATCH_INTERVAL_MAX", 0.010,
        randomize=lambda r: float(r.choice([0.005, 0.010, 0.020])),
    )
    k.define("START_TRANSACTION_BATCH_INTERVAL_SMOOTHER_ALPHA", 0.1)
    k.define("START_TRANSACTION_BATCH_COUNT_MAX", 65536)
    # Bounded GRV front-door queue (the reference's START_TRANSACTION_
    # MAX_QUEUE_SIZE): read-version requests past this depth are SHED
    # with the retryable grv_throttled error instead of queueing
    # unboundedly — overload degrades into delayed admits + client
    # backoff, never into an ever-growing promise list. NOT randomized:
    # ordinary ensemble seeds must not shed by surprise; overload
    # scenarios tighten it explicitly.
    k.define("GRV_PROXY_MAX_QUEUE", 8192)
    # Commit-pipeline depth: how many commit batches may be in flight
    # concurrently through resolve -> tlog-push -> reply, ordered only
    # at the Notified-chain handoffs (the reference bounds pipelining
    # the same way via the resolution/logging version chains).
    k.define("MAX_PIPELINED_COMMIT_BATCHES", 16)
    k.define("RESOLVER_BACKEND", "tpu")  # the resolver_backend knob
    # Below this batch capacity the TPU path cannot win: per-dispatch
    # overhead dominates and the CPU resolves a small batch in well
    # under the device round trip. The default is the MEASURED
    # single-dispatch crossover (scripts/sweep_small.py on v5e in round
    # 5, classic kernel — not re-measured for today's kernel;
    # device-resident p50 vs CPU skiplist p50):
    #   n:            512   2048   8192   16384  32768  65536
    #   device txn/s: 4.2K  16.8K  64K    112K   203K   347K
    #   cpu txn/s:    701K  756K   485K   543K   465K   338K
    # — the device first beats the CPU at n=65536 with inputs
    # device-resident. The RESIDENT basis is deliberate: the TPU
    # resolver operates in GROUPED dispatch with double-buffered
    # staging (~0.9-1.1M txn/s at 64K batches — transfer overlapped
    # with compute), and the sweep's transfer-inclusive numbers pay a
    # single-shot host->device hop per batch. make_conflict_set
    # auto-selects the CPU backend for configs under the threshold — a
    # deliberate, measured TPU-first design decision: the accelerator
    # serves the loaded/batched regime, the CPU serves the latency
    # regime. tests/test_routing_crossover.py pins this decision.
    k.define("RESOLVER_TPU_MIN_BATCH", 65536)
    # Encryption-at-rest (fdbclient/ServerKnobs.cpp ENABLE_ENCRYPTION +
    # fdbserver/EncryptKeyProxy.actor.cpp): storage WAL/checkpoint/LSM
    # payloads are AES-256-CTR sealed under per-domain keys served by
    # the EncryptKeyProxy. Consumed by multiprocess._serve_role; NOT
    # randomized in the sim ensemble — the soak's storage is the
    # in-process sim role, which has no disk to seal (the reference
    # randomizes it because its simulated disks are real files).
    k.define("ENABLE_ENCRYPTION", False)
    # Encryption keys re-derive under a fresh salt after this many
    # seconds (ServerKnobs ENCRYPT_KEY_REFRESH_INTERVAL).
    k.define("ENCRYPT_KEY_REFRESH_INTERVAL", 600.0)
    # Version-vector unicast (default off, like the reference's
    # ENABLE_VERSION_VECTOR_TLOG_UNICAST, fdbclient/ServerKnobs.cpp):
    # resolvers track a per-tlog previous-commit-version vector and
    # replies carry tpcvMap + writtenTags (ResolverInterface.h:140-151).
    k.define("ENABLE_VERSION_VECTOR_TLOG_UNICAST", False)
    # TLog memory budget (in retained mutations) before old unpopped
    # versions spill by reference to the DiskQueue — a lagging storage
    # follower must not grow tlog memory without bound
    # (fdbserver/TLogServer.actor.cpp:2311 + TLOG_SPILL_THRESHOLD)
    k.define(
        "TLOG_SPILL_THRESHOLD", 1_000_000,
        randomize=lambda r: int(r.choice([20, 100, 1_000, 1_000_000])),
    )
    # BUGGIFY: proxies re-send resolve requests (a retry after a lost
    # reply) so the resolver's duplicate-reply window is exercised —
    # Resolver.actor.cpp:513's cached-reply path and the Never() path
    # for requests pruned from the window.
    k.define("BUGGIFY_DUPLICATE_RESOLVE", False)
    # Resolver-generated private mutations + resolver-side txnStateStore
    # (fdbclient/ServerKnobs.cpp:549-550 — randomized under test there too)
    k.define(
        "PROXY_USE_RESOLVER_PRIVATE_MUTATIONS", False,
        randomize=lambda r: bool(r.integers(0, 2)),
    )
    return k


SERVER_KNOBS = make_server_knobs()
