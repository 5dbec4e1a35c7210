"""Plain reference of the scheduler's placement semantics, in numpy.

It imports nothing of the program and reads only the world's arrays, the
pods' controllers and the placements already committed (the history a
batch was scheduled against, like a served model's earlier tokens).  It
states upstream's semantics (kube-scheduler 1.16, generic_scheduler.go
and the default provider) for the shapes the configurations use:

  filter   PodFitsResources: cpu, memory and the pod cap;
  score    the default provider's weighted sum, each priority
           int-truncated as upstream does: SelectorSpread over the pod's
           controller selector (no zone labels, so node counts alone),
           LeastRequested, BalancedResourceAllocation,
           NodePreferAvoidPods (10 at weight 10000), TaintToleration (10),
           and the priorities that are 0 here (InterPodAffinity,
           NodeAffinity, ImageLocality), in the precision the program
           states (float32);
  select   every node is scored (percentageOfNodesToScore 100, so no
           sample); the best score, ties in node order, the k-th tie
           where k is the pod's index in the stream of pods popped
           (selectHost's lastNodeIndex round robin);
  batch    the batch engine's stated contract: every pod of a batch is
           placed against the state the batch started from; where that
           answer overfills a node or leaves a pod unplaced, the batch
           is placed one pod at a time, each against the pods before it
           (upstream's sequential semantics).

`dtype` is the precision of the score arithmetic: float32 as the program
states; the control runs it in bfloat16.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

MAX = 10.0
W_PREFER_AVOID = 10000.0


class State:
    """Committed per-node usage and per-(controller, node) pod counts."""

    def __init__(self, world):
        n = world.n
        self.cpu = np.zeros(n, np.int64)
        self.mem = np.zeros(n, np.int64)
        self.pods = np.zeros(n, np.int64)
        self.count = np.zeros((len(world.controllers), n), np.int64)

    def copy(self) -> "State":
        out = State.__new__(State)
        out.cpu, out.mem = self.cpu.copy(), self.mem.copy()
        out.pods, out.count = self.pods.copy(), self.count.copy()
        return out

    def add(self, world, ctls: np.ndarray, nodes: np.ndarray) -> None:
        ok = nodes >= 0
        c, h = ctls[ok], nodes[ok]
        cnt = np.bincount(h, minlength=world.n)
        self.cpu += cnt * world.pod_cpu
        self.mem += cnt * world.pod_mem
        self.pods += cnt
        np.add.at(self.count, (c, h), 1)


class Reference:
    def __init__(self, world, dtype=np.float32):
        self.w = world
        self.dt = np.dtype(dtype)
        self.state = State(world)
        self.li = 0          # pods popped before the next batch

    def commit(self, ctls: Sequence[int], nodes: Sequence[int]) -> None:
        """Apply one popped batch as it was committed."""
        ctls = np.asarray(ctls, np.int64)
        self.state.add(self.w, ctls, np.asarray(nodes, np.int64))
        self.li += len(ctls)

    def fits(self, st: State, k: int = 1) -> np.ndarray:
        """bool[N]: k more pods of the template fit on the node."""
        w = self.w
        return ((st.cpu + k * w.pod_cpu <= w.cpu)
                & (st.mem + k * w.pod_mem <= w.mem)
                & (st.pods + k <= w.pods))

    def score(self, st: State, c: int) -> np.ndarray:
        """Total score per node in self.dt (priorities/*.go)."""
        dt, w = self.dt.type, self.w
        ten = dt(MAX)

        def least(req, cap):
            per = np.floor(((cap - req) * ten) / cap)
            return np.where(req > cap, dt(0), per)

        rc = (st.cpu + w.pod_cpu).astype(dt)
        rm = (st.mem + w.pod_mem).astype(dt)
        cc, cm = w.cpu.astype(dt), w.mem.astype(dt)
        lr = np.floor((least(rc, cc) + least(rm, cm)) / dt(2))
        fc, fm = rc / cc, rm / cm
        over = (fc >= dt(1)) | (fm >= dt(1))
        bal = np.where(over, dt(0),
                       np.floor((dt(1) - np.abs(fc - fm)) * ten))
        counts = st.count[c].astype(dt)
        mx = counts.max()
        spread = np.floor((ten * (mx - counts)) / max(mx, dt(1))
                          if mx > 0 else np.full_like(counts, ten))
        # the weighted sum in the provider's order: spread, inter-pod
        # affinity (0), least, balanced, prefer-avoid, node affinity (0),
        # taint toleration, image locality (0)
        total = np.zeros(w.n, dt)
        total = total + spread
        total = total + lr
        total = total + bal
        total = total + dt(W_PREFER_AVOID) * ten
        total = total + ten
        return total

    @staticmethod
    def ties(mask: np.ndarray, score: np.ndarray) -> np.ndarray:
        f = np.flatnonzero(mask)
        if len(f) == 0:
            return f
        sc = score[f]
        return f[sc == sc.max()]

    def sequential(self, ctls: Sequence[int], st: State) -> np.ndarray:
        """One pod at a time, each against the pods before it."""
        st = st.copy()
        out = np.full(len(ctls), -1, np.int64)
        for b, c in enumerate(ctls):
            t = self.ties(self.fits(st), self.score(st, int(c)))
            if len(t):
                out[b] = t[(self.li + b) % len(t)]
                st.add(self.w, np.array([c]), out[b:b + 1])
        return out

    def place(self, ctls: Sequence[int]) -> Tuple[np.ndarray, str]:
        """The reference's answer for the next popped batch, against the
        committed state (nothing is committed here).  Returns (node per
        pod, -1 = unschedulable; which rule decided it)."""
        ctls = np.asarray(ctls, np.int64)
        st = self.state
        mask = self.fits(st)
        hosts = np.full(len(ctls), -1, np.int64)
        for c in np.unique(ctls):
            t = self.ties(mask, self.score(st, int(c)))
            if len(t):
                idx = np.flatnonzero(ctls == c)
                hosts[idx] = t[(self.li + idx) % len(t)]
        if (hosts >= 0).all():
            after = st.copy()
            after.add(self.w, ctls, hosts)
            if (after.cpu <= self.w.cpu).all() and (
                    after.mem <= self.w.mem).all() and (
                    after.pods <= self.w.pods).all():
                return hosts, "batch-start"
        return self.sequential(ctls, st), "sequential"
