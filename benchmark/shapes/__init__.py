"""Cluster shapes.  A configuration file names its shape under `"shape"`
(`plain` where it names none); benchmark/shapes/<shape>.py holds what is
particular to it, found by name (harness/byname.py).  A shape module
provides:

  build_world(cfg) -> world
      the cluster as plain arrays, which the reference reads; the world
      has `names`, the node names in registration order;
  capacity(world) -> int
      the pods of the stream the cluster holds;
  warm_sizes(world, batch) -> [int]
      the warm-up's batches, in pods, ahead of its single pods;
  stream(world, n) -> i32[n]
      which controller or pod template each pod of the stream is;
  pod_object(world, cfg, i, t)
      pod i of the stream, of template t, as the program's API object;
  build_encoder(world, cfg)
      the program's SnapshotEncoder with the nodes and selectors;
  Reference(world, dtype)
      the plain reference: `place(ts)` answers a popped batch of stream
      entries ts, (node per pod, -1 = unschedulable; the rule that
      decided), and `commit(ts, nodes)` takes it as committed; `dtype`
      is the score's precision (the control runs bfloat16);
  LIMITS, guarantees(world, node_of) -> {name: number}
      the configuration's guarantees over every bound pod (pod id ->
      node id), each number held to its limit.

The reference imports nothing of the program.
"""
