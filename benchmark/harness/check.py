"""What decides `correct`: the placements the timed path committed,
against the plain reference of the configuration's shape and against the
configuration's guarantees.

Every popped batch, warm-up included, is replayed into the reference's
history in the order the engine saw it, and every one is compared.
Every number compared is exact, so each limit is 0.

The control is the reference in bfloat16 put in the program's place:
`control_placements` makes its answers for the same popped batches, and
they go through the same comparison.

The shape is the module benchmark/shapes/<shape>.py: its
`Reference(world, dtype)` places a popped batch from the batch's stream
entries (`place`) and takes the batch as committed (`commit`), and its
`LIMITS` name the numbers its `guarantees` return.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

LIMITS = {
    "placement_mismatches": 0,
    "failed_pods": 0,
    "degraded_cycles": 0,
}


def limits(shape) -> dict:
    """Every number compared for a cell of this shape, with its limit."""
    return {**LIMITS, **shape.LIMITS}


def compare(shape, world, batches: List[List[int]], ctl_of: np.ndarray,
            node_of: Dict[int, int]) -> dict:
    """Replay `batches` (pod ids in pop order); node_of maps a bound pod
    id to its node id.  Returns the mismatches, how many pods and batches
    were compared, and which rule decided them."""
    ref = shape.Reference(world, dtype=np.float32)
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


def control_placements(shape, world, batches: List[List[int]],
                       ctl_of: np.ndarray, dtype) -> Dict[int, int]:
    """The reference in `dtype`, placing the same popped batches on its
    own history: pod id -> node id for every pod it placed."""
    ref = shape.Reference(world, dtype=dtype)
    out: Dict[int, int] = {}
    for batch in batches:
        ctls = ctl_of[np.asarray(batch, np.int64)]
        hosts, _ = ref.place(ctls)
        out.update((p, int(h)) for p, h in zip(batch, hosts) if h >= 0)
        ref.commit(ctls, hosts)
    return out
