"""Test configuration: force an 8-device virtual CPU mesh before JAX backend init.

The tests run on the CPU whatever the ambient JAX_PLATFORMS says
(jax.config.update wins over the env var); the XLA_FLAGS host-device count
must be set before backend initialization.

Multi-chip sharding (parallel/) is exercised on virtual CPU devices here; the
chip itself is reached through `python chip_smoke.py` (README "Quick start"),
and tests/test_chip_compile.py asks the TPU compiler — no chip attached —
whether the main path's programs compile for a described v5e.

Every test runs under a clock of its own (`clock`), and the tiny
circuit's keys and seeded proofs are built once a worker (the `tiny*`
fixtures; tests/_shapes.py has the shapes they and the kernel tests share).
"""

import contextlib
import faulthandler
import os
import signal
import threading
import types

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# big circuit graphs compile slowly; persist compiled executables across
# runs. One shared policy (dir keyed by host CPU features — foreign AOT
# entries ABORT at load): spectre_tpu.plonk.backend.setup_compile_cache.
from spectre_tpu.plonk.backend import setup_compile_cache  # noqa: E402

setup_compile_cache()

from _shapes import (TINY_K, TINY_SEED, seeded_blinding,  # noqa: E402
                     tiny_circuit, tiny_config)

# ---------------------------------------------------------------------------
# a clock a test

# Seconds one test may take, set-up and tear-down included: over five times
# the slowest test of a run with a cold compile cache (CHANGES.md, PR 31),
# and two of them fit in the tier-1 command's limit.
TEST_LIMIT_S = 300


class ClockExpired(Exception):
    """A test outlived its clock."""


@contextlib.contextmanager
def clock(nodeid: str, limit: float = TEST_LIMIT_S, dump_to=None):
    """Raise `ClockExpired` naming `nodeid` in the main thread once `limit`
    seconds have passed. A wait inside native code never reaches a Python
    signal handler, so at the limit the stacks of all threads are also
    written to `dump_to`, where one is given. On the way out the timer is
    cleared and a clock that was running around this one is set again."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise ClockExpired(f"{nodeid} ran past its {limit:g} s clock")

    handler_before = signal.signal(signal.SIGALRM, expired)
    left_before, _ = signal.setitimer(signal.ITIMER_REAL, limit)
    if dump_to is not None:
        faulthandler.dump_traceback_later(limit, file=dump_to)
    try:
        yield
    finally:
        if dump_to is not None:
            faulthandler.cancel_dump_traceback_later()
        left, _ = signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler_before)
        if left_before:
            signal.setitimer(signal.ITIMER_REAL,
                             max(0.001, left_before - (limit - left)))


_TERMINAL_STDERR = pytest.StashKey[int]()


def pytest_configure(config):
    # the terminal's stderr, before a test's capture stands in for fd 2
    config.stash[_TERMINAL_STDERR] = os.dup(2)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item):
    """The whole of a test under the clock: the wider-scoped fixtures it is
    the first to build, the call, the tear-down. The handler's raise lands
    in whichever of them is running and is that test's failure."""
    with clock(item.nodeid, dump_to=item.config.stash[_TERMINAL_STDERR]):
        yield


# ---------------------------------------------------------------------------
# one tiny circuit, one keygen and one seeded prove a backend (a worker)

@pytest.fixture(scope="session")
def tiny():
    """The tiny circuit at TINY_K with its SRS and CpuBackend proving key."""
    from spectre_tpu.plonk.constraint_system import Assignment
    from spectre_tpu.plonk.keygen import keygen
    from spectre_tpu.plonk.srs import SRS

    cfg = tiny_config()
    advice, lookup, fixed, selectors, copies, out = tiny_circuit(cfg)
    srs = SRS.unsafe_setup(TINY_K)
    return types.SimpleNamespace(
        cfg=cfg, srs=srs, out=out, fixed=fixed, selectors=selectors,
        copies=copies, instances=[[out]],
        asg=Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies),
        pk=keygen(srs, cfg, fixed, selectors, copies))


@pytest.fixture(scope="session")
def tiny_cpu_proof(tiny):
    from spectre_tpu.plonk import backend as B
    from spectre_tpu.plonk.prover import prove
    return prove(tiny.pk, tiny.srs, tiny.asg, B.get_backend("cpu"),
                 blinding_rng=seeded_blinding(TINY_SEED))
