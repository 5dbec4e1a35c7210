"""The main path's programs compile for a TPU v5e, described, not attached.

The TPU compiler is installed here; it compiles for a chip that is only
described (jax.experimental.topologies), so what the chip's compiler would
refuse — or a program that would not fit its 16 GB of HBM — fails here,
at no chip time.  Each program is lowered as the live Scheduler launches
it, with the 5,000-node bench world's shapes, at a batch of 256 so each
compile stays a few seconds.  Nothing runs: this says nothing about
results or times.

The topology is described only inside the module fixture: the first call
loads libtpu, which one process at a time may hold.
"""

import os

import numpy as np
import pytest

import jax

from __graft_entry__ import _build_bench_world

HBM_BYTES = 16e9
N_NODES = 5000
BATCH = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def world():
    from kubernetes_tpu.models.batched import encode_batch_ports

    enc, pending = _build_bench_world(N_NODES, BATCH)
    batch = enc.encode_pods(pending)
    return {
        "batch": batch,
        "ports": encode_batch_ports(enc, pending),
        "cluster": enc.snapshot(),
        "kw": dict(
            unsched_taint_key=enc.interner.intern(
                "node.kubernetes.io/unschedulable"),
            zone_key_id=enc.getzone_key,
            quality_topk=3,  # the Scheduler's default
        ),
    }


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), np.asarray(a).dtype, sharding=sharding),
        tree,
    )


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BYTES, used


def test_filter_score_select_compiles(one_chip, world):
    from kubernetes_tpu.codec.schema import FilterConfig
    from kubernetes_tpu.ops import (
        filter_batch,
        score_batch,
        select_hosts_batch,
    )

    cfg, kw = FilterConfig(), world["kw"]

    def fwd(cluster, pods):
        mask, _ = filter_batch(cluster, pods, cfg, kw["unsched_taint_key"])
        total, _ = score_batch(cluster, pods, zone_key_id=kw["zone_key_id"])
        return select_hosts_batch(total, mask, 0)

    _fits(jax.jit(fwd).lower(
        _shapes(world["cluster"], one_chip), _shapes(world["batch"], one_chip),
    ).compile())


def test_speculative_packed_program_compiles(one_chip, world):
    from kubernetes_tpu.codec.transfer import pack_tree
    from kubernetes_tpu.models.speculative import make_speculative_scheduler

    fn = make_speculative_scheduler(**world["kw"])
    ports = world["ports"]
    bufs, meta = pack_tree(
        {"pods": world["batch"], "pp": ports.pod_ports, "cf": ports.conflict},
        factor=True,  # the chip's packing
    )
    _fits(fn.packed(meta).lower(
        _shapes(world["cluster"], one_chip), _shapes(bufs, one_chip),
        jax.ShapeDtypeStruct((), np.int32, sharding=one_chip),
    ).compile())


def test_sequential_scan_compiles(one_chip, world):
    from kubernetes_tpu.models.batched import make_sequential_scheduler

    fn = make_sequential_scheduler(**world["kw"])
    _fits(fn.program.lower(
        _shapes(world["cluster"], one_chip), _shapes(world["batch"], one_chip),
        _shapes(world["ports"], one_chip),
        jax.ShapeDtypeStruct((), np.int32, sharding=one_chip),
        None, None, None, None,
    ).compile())


def test_dirty_row_scatter_compiles(one_chip, world):
    from kubernetes_tpu.codec.transfer import _scatter_donate

    req = np.asarray(world["cluster"].requested)
    rows = np.arange(BATCH, dtype=np.int32)
    _fits(_scatter_donate.lower(
        _shapes(req, one_chip), _shapes(rows, one_chip),
        _shapes(req[:BATCH], one_chip),
    ).compile())
