"""Persistent XLA compilation cache (VERDICT r1 task 10).

The driver re-runs bench.py in a fresh process every round; without a
persistent cache each run re-pays the full trace+compile of the resolver
kernel (minutes at 64K-txn shapes). JAX's persistent
cache keys on (HLO, compile options, backend version), so a warm cache
drops that to de/serialization time.
"""

from __future__ import annotations

import os
import threading

from foundationdb_tpu.utils.probes import code_probe, declare

declare("perf.compile_cache_miss")

#: the fixed cache dir when JAX_COMPILATION_CACHE_DIR is not set — the
#: path is part of JAX's cache key, so it must not move between runs
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_compile_cache")
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def _host_feature_lines() -> str:
    """The host identity XLA:CPU AOT entries are sensitive to: ISA
    feature lines PLUS the CPU model name. The model name matters —
    XLA derives microarchitecture tuning pseudo-features from it
    (`prefer-no-gather`/`prefer-no-scatter`), so two hosts with
    byte-identical cpuinfo FLAGS can still produce incompatible AOT
    entries (the MULTICHIP_r05 cpu_aot_loader mismatch spam). MHz /
    bogomips lines stay out: per-boot noise would invalidate the cache
    on every restart of the same host."""
    import platform

    lines = set()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features", "model name")):
                    lines.add(line.strip())
    except OSError:
        pass
    return "|".join(sorted(lines)) or platform.processor()


#: sentinel recording which host populated a cache dir (the scrub key)
_FINGERPRINT_NAME = "HOST_FINGERPRINT"


def host_fingerprint() -> str:
    """Hash of this host's CPU identity (ISA feature lines + model
    name): the key for anything holding host machine code."""
    import hashlib
    import platform

    return hashlib.md5(
        (platform.machine() + ":" + _host_feature_lines()).encode()
    ).hexdigest()


def scrub_on_host_mismatch(path: str) -> bool:
    """Drop a persistent-cache dir's entries when its recorded host
    fingerprint doesn't match THIS host; stamp the current fingerprint
    either way. Returns whether a scrub happened.

    The cache dir is fixed, so a copy of the repo that moves to another
    host carries its cache along: loading another
    machine's XLA:CPU AOT entries spams machine-feature-mismatch errors
    on stderr — which polluted the multichip lane's JSON `tail`
    (MULTICHIP_r05) — and risks SIGILL. Scrubbing trades one warm cache
    for a clean, safe run on the new host."""
    marker = os.path.join(path, _FINGERPRINT_NAME)
    want = host_fingerprint()
    try:
        with open(marker) as f:
            have = f.read().strip()
    except OSError:
        have = None
    try:
        entries = [n for n in os.listdir(path) if n != _FINGERPRINT_NAME]
    except OSError:
        entries = []
    scrubbed = False
    # An UNSTAMPED dir that already holds entries cannot be proven
    # local: a container baked before the marker existed carries
    # another machine's AOT entries with no stamp at all — exactly the
    # migrating scenario this scrub exists for. Conservatively scrub
    # (one re-warm beats a SIGILL risk); an empty dir just gets
    # stamped.
    if (have is not None and have != want) or (have is None and entries):
        import shutil

        for name in os.listdir(path):
            if name == _FINGERPRINT_NAME:
                continue
            victim = os.path.join(path, name)
            try:
                if os.path.isdir(victim):
                    shutil.rmtree(victim, ignore_errors=True)
                else:
                    os.remove(victim)
            except OSError:
                pass  # a straggler entry keeps its warning; never fatal
        scrubbed = True
        from foundationdb_tpu.utils.trace import SEV_WARN, TraceEvent

        TraceEvent("CompileCacheScrubbed", severity=SEV_WARN).detail(
            "Path", path
        ).detail("RecordedFingerprint", have or "unstamped").detail(
            "HostFingerprint", want
        ).log()
    if have != want:
        try:
            with open(marker, "w") as f:
                f.write(want + "\n")
        except OSError:
            pass
    return scrubbed


def enable(path: str | None = None) -> str:
    """Turn on the persistent compilation cache; returns the cache dir.

    When JAX_COMPILATION_CACHE_DIR is set, JAX already reads its cache
    dir from it and this leaves the dir alone (no override, no scrub).
    Otherwise the cache goes to `path` or the fixed DEFAULT_DIR, after
    a scrub of entries another host wrote (see
    `scrub_on_host_mismatch`). Safe to call multiple times and
    before/after backend init (the cache is consulted at compile time).
    Also arms the compile-observability listeners (`instrument()`), so
    every enabled process carries hit/miss counters and compile seconds
    in `stats()`.
    """
    import jax

    env_dir = os.environ.get(ENV_DIR)
    if env_dir:
        path = env_dir
    else:
        path = path or DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        scrub_on_host_mismatch(path)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache everything: the kernel's many specializations are each well
    # over the default thresholds anyway, and tiny entries are harmless.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    instrument()
    return path


# ---------------------------------------------------------------------------
# Compile observability (ISSUE 10): JAX emits monitoring events for
# persistent-cache hits/misses and backend-compile durations; this
# module aggregates them into one process-global stats block that
# KernelStageMetrics.qos() / cluster_status() / the perf ledger read.
# Process-global on purpose — the XLA compiler and its cache are too.
# These counters are wall-clock/host-dependent and deliberately stay
# OUT of every CounterCollection the deterministic trace flush ships.

_stats_lock = threading.Lock()
_stats = {
    "cache_hits": 0,
    "cache_misses": 0,
    "backend_compiles": 0,
    "compile_seconds_total": 0.0,
    "last_compile_seconds": 0.0,
}
#: explicit per-signature compile seconds (warm-compile paths that know
#: what they compiled record here; the monitoring listener only knows
#: durations, not signatures)
_signatures: dict[str, float] = {}
_instrumented = False

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_event(event: str, *a, **kw) -> None:
    if event == _HIT_EVENT:
        with _stats_lock:
            _stats["cache_hits"] += 1
    elif event == _MISS_EVENT:
        with _stats_lock:
            _stats["cache_misses"] += 1
        code_probe(True, "perf.compile_cache_miss")


def _on_duration(event: str, duration: float, *a, **kw) -> None:
    if event.endswith("backend_compile_duration"):
        with _stats_lock:
            _stats["backend_compiles"] += 1
            _stats["compile_seconds_total"] += float(duration)
            _stats["last_compile_seconds"] = float(duration)


def instrument() -> bool:
    """Register the jax.monitoring listeners (idempotent). Returns
    whether the listeners are armed — an older/newer JAX without the
    monitoring API degrades to zeros, never an error."""
    global _instrumented
    if _instrumented:
        return True
    try:
        from jax import monitoring

        # resolve BOTH registrars before registering either: failing
        # between the two would leave _instrumented False and a later
        # enable() would register _on_event twice (double counts)
        reg = monitoring.register_event_listener
        reg_duration = monitoring.register_event_duration_secs_listener
    except Exception:
        return False
    _instrumented = True  # before the calls: never re-register
    reg(_on_event)
    reg_duration(_on_duration)
    return True


def record_compile(signature: str, seconds: float) -> None:
    """Per-signature compile seconds, recorded by the code paths that
    know WHAT they compiled (ResolverRole warm compile, bench warm
    loops). Keeps the most recent duration per signature."""
    with _stats_lock:
        _signatures[signature] = float(seconds)


def stats() -> dict:
    """One snapshot: cache hit/miss counters, backend-compile count and
    seconds, and the per-signature compile-seconds map."""
    with _stats_lock:
        out = dict(_stats)
        out["per_signature_compile_seconds"] = dict(_signatures)
    return out


def reset_stats() -> None:
    """Test hook: zero the process-global counters."""
    with _stats_lock:
        for k in _stats:
            _stats[k] = 0.0 if isinstance(_stats[k], float) else 0
        _signatures.clear()
