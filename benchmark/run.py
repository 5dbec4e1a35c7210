"""The benchmark's entry: one run of one cell.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It holds the chip for the whole run and never falls back to the CPU: with
no accelerator it exits non-zero and prints no result.  The last line of
standard output is the contract's JSON result.  BENCHMARK.json names the
cells; benchmark/configs, benchmark/traffic and benchmark/metrics hold
what each cell, mix and per-layer metric needs, found by name.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the compile cache lives at one fixed path inside the checkout; the
# program takes this directory (utils/compilecache.py reads the variable)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path.insert(0, ROOT)

from benchmark.harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], ROOT, T_START))
