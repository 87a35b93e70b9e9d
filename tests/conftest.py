"""Test configuration: force an 8-device virtual CPU mesh before JAX backend init.

The tests run on the CPU whatever the ambient JAX_PLATFORMS says
(jax.config.update wins over the env var); the XLA_FLAGS host-device count
must be set before backend initialization.

Multi-chip sharding (parallel/) is exercised on virtual CPU devices here; the
chip itself is reached through `python chip_smoke.py` (README "Quick start"),
and tests/test_chip_compile.py asks the TPU compiler — no chip attached —
whether the main path's programs compile for a described v5e.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# big circuit graphs compile slowly; persist compiled executables across
# runs. One shared policy (dir keyed by host CPU features — foreign AOT
# entries ABORT at load): spectre_tpu.plonk.backend.setup_compile_cache.
from spectre_tpu.plonk.backend import setup_compile_cache  # noqa: E402

setup_compile_cache()
