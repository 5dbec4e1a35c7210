"""Traffic kinds.  A mix is a data file of parameters,
benchmark/traffic/<name>.json, whose `"kind"` names the module here
that reads it: benchmark/kinds/<kind>.py, found by name
(harness/byname.py).  A kind module provides:

  window_pods(traffic, seconds, seed, room) -> (n, plan)
      how many pods the window offers, at most `room` (what the cluster
      holds after the warm-up), and what its window needs to offer them
      (arrival offsets, or None);
  window(sched, queue, seams, pods, plan, metrics_mod, traffic, seconds,
         batch, tracer, snap_fn) -> dict
      the measured window over the window's pods, through
      harness/drive.py's run_cycle and Tracer; the dict has at least
      t_open, t_close (clock()) and added (pods offered);
  end_to_end(w, seams, offered, popped, seconds, log)
          -> (numbers, attempted)
      the kind's end-to-end numbers (BENCHMARK.json names) from the
      window's dict, the seams, the names of the pods offered and of the
      pods popped in the window; `attempted` names the pods they count.

The warm-up is the harness's and the shape's, the same for every kind
(main.py).
"""
