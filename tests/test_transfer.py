"""Packed transfers (codec/transfer.py): pack/unpack round-trip and the
incremental DeviceSnapshotCache reuse semantics."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.codec.transfer import (
    DeviceSnapshotCache,
    pack_tree,
    unpack_tree,
)

from fixtures import TEST_DIMS, make_node, make_pod


def test_pack_unpack_roundtrip():
    tree = {
        "f": np.arange(12, dtype=np.float32).reshape(3, 4),
        "i": np.arange(6, dtype=np.int32).reshape(2, 3),
        "b": np.array([[True, False], [False, True]]),
        "i64": np.arange(4, dtype=np.int64),
    }
    bufs, meta = pack_tree(tree)
    assert len(bufs) == 3

    @jax.jit
    def rt(bufs):
        return unpack_tree(bufs, meta)

    out = rt(bufs)
    np.testing.assert_array_equal(np.asarray(out["f"]), tree["f"])
    np.testing.assert_array_equal(np.asarray(out["i"]), tree["i"])
    np.testing.assert_array_equal(np.asarray(out["b"]), tree["b"])
    np.testing.assert_array_equal(np.asarray(out["i64"]), tree["i64"])
    assert out["b"].dtype == jnp.bool_


def test_pack_meta_is_jit_cache_stable():
    a = {"x": np.zeros((4, 4), np.float32), "y": np.ones(3, np.int32)}
    b = {"x": np.ones((4, 4), np.float32), "y": np.zeros(3, np.int32)}
    _, ma = pack_tree(a)
    _, mb = pack_tree(b)
    assert ma == mb and hash(ma) == hash(mb)


def test_device_snapshot_cache_reuses_unchanged_fields():
    enc = SnapshotEncoder(TEST_DIMS)
    for i in range(4):
        enc.add_node(make_node(f"n{i}", cpu="4", mem="8Gi"))
    cache = DeviceSnapshotCache()
    d1 = cache.update(enc.snapshot())
    # a pod commit moves requested/nonzero but not the label/taint tensors
    enc.add_pod(make_pod("p0", cpu="500m", mem="512Mi", node_name="n1"))
    d2 = cache.update(enc.snapshot())
    assert d2.label_keys is d1.label_keys          # resident buffer reused
    assert d2.taint_key is d1.taint_key
    assert d2.requested is not d1.requested        # changed -> re-uploaded
    row = enc.node_rows["n1"]
    assert np.asarray(d2.requested)[row, 0] == 500.0
    # device contents always match a fresh full upload
    full = enc.snapshot()
    for f in dataclasses.fields(full):
        np.testing.assert_array_equal(
            np.asarray(getattr(d2, f.name)), np.asarray(getattr(full, f.name)),
            err_msg=f.name,
        )


def test_device_snapshot_cache_handles_regrow():
    enc = SnapshotEncoder(TEST_DIMS)
    enc.add_node(make_node("n0", cpu="4", mem="8Gi"))
    cache = DeviceSnapshotCache()
    d1 = cache.update(enc.snapshot())
    n1 = d1.valid.shape[0]
    for i in range(1, 3 * n1):  # force at least one node-arena regrow
        enc.add_node(make_node(f"n{i}", cpu="4", mem="8Gi"))
    d2 = cache.update(enc.snapshot())
    assert d2.valid.shape[0] > n1
    assert int(np.asarray(d2.valid).sum()) == 3 * n1


def test_pack_tree_row_factoring_roundtrip():
    """Large [B, ...] leaves with repeated rows ship factored (unique rows
    + index) and unpack bit-identically; unique-rowed leaves bail out and
    ship dense; small leaves are untouched.  factor=True forces the
    accelerator path on the CPU backend."""
    rng = np.random.default_rng(7)
    base = rng.integers(0, 2, size=(20, 16384)).astype(bool)     # 20 rows
    rep_b = base[rng.integers(0, 20, size=2048)]                 # 32MB dense
    rep_f = (base.astype(np.float32) * 3.5)[rng.integers(0, 20, size=2048)]
    uniq_f = rng.random((512, 4096)).astype(np.float32)          # no repeats
    small = rng.integers(0, 100, size=(64,)).astype(np.int32)
    tree = {"rb": rep_b, "rf": rep_f, "u": uniq_f, "s": small}
    bufs, meta = pack_tree(tree, factor=True)
    # the wire payload collapsed: repeated leaves cost ~U rows, not B
    assert sum(b.nbytes for b in bufs) < rep_b.nbytes
    out = jax.jit(lambda b: unpack_tree(b, meta))(bufs)
    for k, v in tree.items():
        np.testing.assert_array_equal(np.asarray(out[k]), v, err_msg=k)
    # meta is stable across batches of the same workload shape/content mix
    rep_b2 = base[rng.integers(0, 20, size=2048)]
    rep_f2 = (base.astype(np.float32) * 3.5)[rng.integers(0, 20, size=2048)]
    _, meta2 = pack_tree(
        {"rb": rep_b2, "rf": rep_f2, "u": uniq_f, "s": small}, factor=True
    )
    assert meta2 == meta
    # factor=False (the CPU default) keeps the legacy dense packing
    bufs_d, meta_d = pack_tree(tree, factor=False)
    out_d = jax.jit(lambda b: unpack_tree(b, meta_d))(bufs_d)
    for k, v in tree.items():
        np.testing.assert_array_equal(np.asarray(out_d[k]), v, err_msg=k)


def test_pack_tree_factoring_randomized_property():
    """Property soak: for random mixes of repeated/unique/odd-shaped
    leaves, factor=True and factor=False unpack to identical trees."""
    for seed in range(6):
        rng = np.random.default_rng(100 + seed)
        tree = {}
        for i in range(rng.integers(2, 6)):
            kind = rng.integers(0, 3)
            B = int(rng.choice([64, 257, 1024]))
            w = int(rng.choice([512, 2048, 4096]))
            if kind == 0:  # group-repeated rows
                g = int(rng.integers(1, 9))
                base = rng.random((g, w)).astype(np.float32)
                tree[f"r{i}"] = base[rng.integers(0, g, size=B)]
            elif kind == 1:  # unique rows
                tree[f"u{i}"] = rng.integers(
                    0, 2, size=(B, w)).astype(bool)
            else:  # small leaf
                tree[f"s{i}"] = rng.integers(
                    0, 50, size=(int(rng.integers(1, 64)),)
                ).astype(np.int32)
        bufs_f, meta_f = pack_tree(tree, factor=True)
        bufs_d, meta_d = pack_tree(tree, factor=False)
        out_f = jax.jit(lambda b: unpack_tree(b, meta_f))(bufs_f)
        out_d = jax.jit(lambda b: unpack_tree(b, meta_d))(bufs_d)
        for k, v in tree.items():
            np.testing.assert_array_equal(np.asarray(out_f[k]), v,
                                          err_msg=f"seed {seed} {k}")
            np.testing.assert_array_equal(np.asarray(out_d[k]), v,
                                          err_msg=f"seed {seed} {k}")


# ------------------------------------------------------- fetch worker


def test_fetch_worker_survives_raising_job():
    """Regression: a job that raises on the shared fetch worker used to
    kill the daemon thread, stranding every already-queued fetch (their
    AsyncFetch.result() hung forever).  Per-job exceptions must be
    contained and the worker must keep draining."""
    from kubernetes_tpu.codec.transfer import AsyncFetch, _fetch_worker

    w = _fetch_worker()
    w.submit(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    # a fetch queued AFTER the poison job still completes promptly
    f = AsyncFetch(np.arange(8, dtype=np.int32))
    deadline = 2.0
    import time as _t
    t0 = _t.monotonic()
    out = f.result()
    assert _t.monotonic() - t0 < deadline
    np.testing.assert_array_equal(out, np.arange(8, dtype=np.int32))
    assert w.thread.is_alive()


def test_async_fetch_routes_job_error_into_handle():
    """An error raised while materializing re-raises at result() — the
    owning handle, not the worker thread, owns the failure."""
    from kubernetes_tpu.codec.transfer import AsyncFetch

    class Evil:
        def __array__(self, *a, **k):
            raise RuntimeError("UNAVAILABLE: device link reset")

    f = AsyncFetch(Evil())
    with np.testing.assert_raises(RuntimeError):
        f.result()
    # and the worker still serves later fetches
    g = AsyncFetch(np.ones(3, np.float32))
    np.testing.assert_array_equal(g.result(), np.ones(3, np.float32))


def test_device_snapshot_cache_invalidate_forces_full_reupload():
    enc = SnapshotEncoder(TEST_DIMS)
    for i in range(4):
        enc.add_node(make_node(f"n{i}", cpu="4", mem="8Gi"))
    cache = DeviceSnapshotCache()
    d1 = cache.update(enc.snapshot())
    cache.invalidate()
    d2 = cache.update(enc.snapshot())
    # no resident buffer survived: every field re-uploaded (new objects)
    assert d2.label_keys is not d1.label_keys
    np.testing.assert_array_equal(
        np.asarray(d2.requested), np.asarray(d1.requested)
    )
