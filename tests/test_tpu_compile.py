"""The resolver's device programs compile for a described TPU v5e.

No chip is attached: `jax.experimental.topologies` describes a v5e:2x2
and the TPU compiler (installed with libtpu) compiles each program of
the main path for it — the tiered kernel in its plain, read-dedup and
range-sweep forms, `delta.compact`, and the 4-device mesh-sharded tiered
program. What the chip's compiler would refuse (a tiling, fast memory,
a program that does not fit) fails here, at no chip time. At the small
shapes below each compile takes seconds.

`python tests/test_tpu_compile.py` compiles the same programs at the
sizes chip_smoke.py runs (plus the latch fallbacks and phase A's served
kernel) and prints compile seconds and `memory_analysis()` per program.

Only one process at a time may load libtpu, so the topology is described
inside a fixture, never at import: every xdist worker collects the same
tests and only the worker given this file loads the library.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "scripts")]

#: (bench mode, txns per batch) per program family at test size
SMALL = {"uniform": 256, "zipf": 256, "ycsb_e": 256, "sharded": 256}
#: the sizes chip_smoke.py runs
FULL = {"uniform": 65536, "zipf": 65536, "ycsb_e": 16384, "sharded": 65536}
FUSE = 8


def describe_topology():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )


def _sds(tree, sharding):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _stream(mode: str, n_txns: int):
    """(config, stacked host args of one fused group) at bench shapes."""
    import bench
    from foundationdb_tpu.utils.packing import stack_device_args

    config, batches, _, _ = bench.build_stream(mode, n_txns, FUSE, fuse=FUSE)
    if mode == "zipf" and not config.dedup_reads:
        # small zipf streams are too distinct for bench to size dedup on
        config = dataclasses.replace(config, dedup_reads=n_txns // 2)
    return config, stack_device_args(batches)


def programs(topo, sizes: dict, full: bool = False) -> dict:
    """name -> (jitted fn, abstract args) for the described devices."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import numpy as np

    from foundationdb_tpu.models import conflict_set as CS
    from foundationdb_tpu.ops import delta as D
    from foundationdb_tpu.parallel import sharding as SH

    one = SingleDeviceSharding(topo.devices[0])
    out = {}

    def tiered(name, mode, *, exact=False):
        config, args = _stream(mode, sizes[mode])
        state = _sds(jax.eval_shape(lambda: D.init(config)), one)
        latch = config.fixpoint_latch and not exact
        fn = CS._resolve_tiered_jit(
            0, config.fixpoint_unroll, latch,
            0 if exact else config.dedup_reads, config.range_sweep,
        )
        out[name] = (fn, (state, _sds(args, one)))
        return config, state

    _, state = tiered("tiered_plain", "uniform")
    out["compact"] = (CS._COMPACT, (state,))
    tiered("tiered_dedup", "zipf")
    tiered("tiered_sweep", "ycsb_e")
    if full:
        tiered("tiered_dedup_exact", "zipf", exact=True)
        tiered("tiered_sweep_exact", "ycsb_e", exact=True)
        # phase A's served kernel: one batch per dispatch, 16-byte keys
        import bench_pipeline

        from foundationdb_tpu.models.types import CommitTransaction
        from foundationdb_tpu.utils import packing

        served = bench_pipeline.kernel_config(sizes["uniform"])
        one_txn = CommitTransaction(
            read_conflict_ranges=[(b"a", b"b")],
            write_conflict_ranges=[(b"a", b"b")], read_snapshot=0,
        )
        args = packing.stack_device_args(
            [packing.pack_batch([one_txn], 1, 0, served)]
        )
        sstate = _sds(jax.eval_shape(lambda: D.init(served)), one)
        out["served_g1"] = (CS._resolve_tiered_jit(0, 3, False, 0, False),
                            (sstate, _sds(args, one)))
        out["served_compact"] = (CS._COMPACT, (sstate,))

    n = len(topo.devices)
    mesh = Mesh(np.array(topo.devices), (SH.AXIS,))
    config, args = _stream("uniform", sizes["sharded"])
    config = dataclasses.replace(config, n_shards=n)
    shard = NamedSharding(mesh, P(SH.AXIS))
    single = jax.eval_shape(lambda: D.init(config))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype,
                                       sharding=shard),
        single,
    )
    part = jax.ShapeDtypeStruct((n, config.key_words), np.uint32,
                                sharding=shard)
    out["sharded_tiered"] = (
        SH.tiered_sharded_jit(mesh, 0, config.fixpoint_unroll, False, 0),
        (state, _sds(args, NamedSharding(mesh, P())), part, part),
    )
    out["sharded_compact"] = (SH.compact_sharded_jit(mesh), (state,))
    return out


@pytest.fixture(scope="module")
def topo():
    try:
        return describe_topology()
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    """Every program compiled once, with the persistent cache off: a
    described-chip compile is written to it but can never be read back
    without a chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield {
            name: fn.lower(*args).compile()
            for name, (fn, args) in programs(topo, SMALL).items()
        }
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("name", [
    "tiered_plain", "tiered_dedup", "tiered_sweep", "compact",
    "sharded_tiered", "sharded_compact",
])
def test_program_compiles_for_v5e(compiled, name):
    mem = compiled[name].memory_analysis()
    assert mem is not None
    assert mem.argument_size_in_bytes > 0


def test_sharded_program_combines_across_the_mesh(compiled):
    """The sharded kernel is one program over 4 chips whose verdicts
    are combined by collectives, not gathered to one device."""
    text = compiled["sharded_tiered"].as_text()
    assert "all-reduce" in text


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    topo = describe_topology()
    for name, (fn, args) in programs(topo, FULL, full=True).items():
        t0 = time.perf_counter()
        mem = fn.lower(*args).compile().memory_analysis()
        print(f"{name}: compile {time.perf_counter() - t0:.1f}s "
              f"args {mem.argument_size_in_bytes} out "
              f"{mem.output_size_in_bytes} temp {mem.temp_size_in_bytes} "
              f"code {mem.generated_code_size_in_bytes}", flush=True)


if __name__ == "__main__":
    main()
