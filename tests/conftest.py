"""Test configuration: force an 8-device virtual CPU platform.

The reference's CI runs single-process CPU-only tests and leaves all distributed
behavior untested (SURVEY.md §4). JAX lets us do better: every mesh/collective code
path runs against 8 virtual CPU devices here.

The shared recipe in ``__graft_entry__`` pins the platform and the device count
before the first device query, whatever the environment asked for.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _force_cpu_platform  # noqa: E402

jax = _force_cpu_platform(8)
jax.config.update("jax_default_matmul_precision", "float32")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from trlx_tpu.parallel.mesh import make_mesh

    return make_mesh(data=2, fsdp=2, model=2)
