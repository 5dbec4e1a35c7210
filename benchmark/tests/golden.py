"""The plain shape's outputs reduced to digests, for the golden test in
test_shapes.py.

`plain_parts` reaches the shape through an adapter (the functions named
in its arguments), so the same reduction could run over the harness as
it was before benchmark/shapes existed (world.py, reference.py,
check.py and main.py's warm-up list): data/plain_golden.json holds the
digests that run gave.  The seam has to give the same ones.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json

import ml_dtypes
import numpy as np

# the fixed popped stream: batches of these widths in turn over a stream
# 50 pods longer than the cluster holds, so that the reference's
# batch-start rule decides most batches and its sequential rule the
# last ones (a node overfilled, then pods left unplaced)
WIDTHS = (64, 64, 1, 33, 64, 17, 64, 5)
OVERFLOW = 50


def batches_of(n: int):
    out, k = [], 0
    for w in itertools.cycle(WIDTHS):
        if k >= n:
            return out
        out.append(list(range(k, min(n, k + w))))
        k += w


def _digest(obj) -> str:
    text = json.dumps(obj, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()


def _plain(v):
    if isinstance(v, np.ndarray):
        return [v.dtype.str, v.tolist()]
    return v


def _recorded_encoder(build, world, cfg):
    """The calls build_encoder makes on the encoder: every node object
    field by field, and every spread selector."""
    from kubernetes_tpu.codec.encoder import SnapshotEncoder

    calls = []
    add_nodes = SnapshotEncoder.add_nodes
    add_sel = SnapshotEncoder.add_spread_selector

    def rec_nodes(self, nodes):
        calls.append(["add_nodes", [repr(n) for n in nodes]])
        return add_nodes(self, nodes)

    def rec_sel(self, *a, **kw):
        calls.append(["add_spread_selector", repr(a),
                      repr(sorted(kw.items()))])
        return add_sel(self, *a, **kw)

    SnapshotEncoder.add_nodes = rec_nodes
    SnapshotEncoder.add_spread_selector = rec_sel
    try:
        build(world, cfg)
    finally:
        SnapshotEncoder.add_nodes = add_nodes
        SnapshotEncoder.add_spread_selector = add_sel
    return calls


def plain_parts(cfg: dict, batch: int, *, build_world, capacity, warm_sizes,
                stream, pod_object, build_encoder, place, compare,
                guarantees) -> dict:
    """Everything the harness takes from the shape, as plain values:
    the world's arrays, the capacity, the warm-up widths, the stream, the
    pod objects field by field, the encoder's build, and the reference's
    placements (float32 and the bfloat16 control), rules and guarantees
    over the fixed popped stream."""
    world = build_world(cfg)
    cap = capacity(world)
    n = cap + OVERFLOW
    ts = stream(world, n)
    pods = [pod_object(world, cfg, i, ts[i]) for i in range(n)]
    batches = batches_of(n)
    node_of = place(world, batches, ts, np.float32)
    cmp = compare(world, batches, ts, node_of)
    control = place(world, batches, ts, ml_dtypes.bfloat16)
    return {
        "world": {f.name: _plain(getattr(world, f.name))
                  for f in dataclasses.fields(world)},
        "capacity": cap,
        "warm_sizes": list(warm_sizes(world, batch)),
        "stream": _plain(ts),
        "pods": [[repr(p), sorted(vars(p))] for p in pods],
        "encoder": _recorded_encoder(build_encoder, world, cfg),
        "placements": sorted(node_of.items()),
        "control_placements": sorted(control.items()),
        "compare": cmp,
        "guarantees": guarantees(world, node_of),
    }


def digests(parts: dict) -> dict:
    """A digest per part, and the small parts as they are."""
    return {
        "digests": {k: _digest(v) for k, v in parts.items()},
        "capacity": parts["capacity"],
        "warm_sizes": parts["warm_sizes"],
        "compare": parts["compare"],
        "guarantees": parts["guarantees"],
    }
