"""What decides `correct`: the placements the timed path committed,
against the plain reference and against the configuration's guarantees.

Every popped batch, warm-up included, is replayed into the reference's
history in the order the engine saw it, and every one is compared.
Every number compared is exact, so each limit is 0.

The control is the reference in bfloat16 put in the program's place:
`control_placements` makes its answers for the same popped batches, and
they go through the same comparison.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.harness.reference import Reference

LIMITS = {
    "placement_mismatches": 0,
    "failed_pods": 0,
    "degraded_cycles": 0,
    "overcommitted_nodes": 0,
}


def compare(world, batches: List[List[int]], ctl_of: np.ndarray,
            node_of: Dict[int, int]) -> dict:
    """Replay `batches` (pod ids in pop order); node_of maps a bound pod
    id to its node id.  Returns the mismatches, how many pods and batches
    were compared, and which rule decided them."""
    ref = Reference(world, dtype=np.float32)
    mism = 0
    how: Dict[str, int] = {}
    n_pods = 0
    for batch in batches:
        ctls = ctl_of[np.asarray(batch, np.int64)]
        got = np.array([node_of.get(p, -1) for p in batch], np.int64)
        want, rule = ref.place(ctls)
        mism += int((want != got).sum())
        how[rule] = how.get(rule, 0) + 1
        n_pods += len(batch)
        ref.commit(ctls, got)
    return {"mismatches": mism, "pods": n_pods, "batches": len(batches),
            "rules": how}


def control_placements(world, batches: List[List[int]],
                       ctl_of: np.ndarray, dtype) -> Dict[int, int]:
    """The reference in `dtype`, placing the same popped batches on its
    own history: pod id -> node id for every pod it placed."""
    ref = Reference(world, dtype=dtype)
    out: Dict[int, int] = {}
    for batch in batches:
        ctls = ctl_of[np.asarray(batch, np.int64)]
        hosts, _ = ref.place(ctls)
        out.update((p, int(h)) for p, h in zip(batch, hosts) if h >= 0)
        ref.commit(ctls, hosts)
    return out


def guarantees(world, node_of: Dict[int, int]) -> dict:
    """The configuration's guarantees over every bound pod, from the
    world's arrays alone."""
    nodes = np.fromiter(node_of.values(), np.int64, len(node_of))
    cnt = np.bincount(nodes, minlength=world.n)
    over = ((cnt * world.pod_cpu > world.cpu)
            | (cnt * world.pod_mem > world.mem)
            | (cnt > world.pods))
    return {"overcommitted_nodes": int(over.sum())}
