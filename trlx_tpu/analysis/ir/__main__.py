"""Deviceless entry: force a virtual CPU platform, then run the audit.

The device count must be pinned BEFORE jax initializes a backend; the recipe
is ``__graft_entry__._force_cpu_platform``'s, as in ``tests/conftest.py``.
"""

import os
import sys


def _force_cpu(n_devices: int):
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.extend.backend

    jax.extend.backend.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n_devices:
        raise SystemExit(
            f"graftcheck-ir: needs >= {n_devices} cpu devices, got "
            f"{len(devs)} x {devs[0].platform} (was jax imported before -m?)"
        )


if __name__ == "__main__":
    _force_cpu(int(os.environ.get("TRLX_IR_DEVICES", "8")))
    from trlx_tpu.analysis.ir.cli import main

    sys.exit(main())
