"""Test fixture: force an 8-device virtual CPU mesh.

Tests and development run on the CPU; the chip is reached only through
the chip tool (`python chip_smoke.py`).  force_cpu_mesh selects the cpu
platform through jax.config (whatever JAX_PLATFORMS says) and requests 8
virtual host devices, matching the driver's dryrun_multichip environment.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubernetes_tpu.utils.jaxenv import force_cpu_mesh

force_cpu_mesh(8)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(20260729)
