"""The shapes the kernel byte-equality tests share.

XLA:CPU compiles one program a shape of a kernel, and for the MSM kernels
that is tens of seconds each with a cold compile cache: the test tier's
time is the number of distinct shapes it asks for, not the work it does. So
every test that compares a device kernel with its oracle does it at ONE
shape a kernel, defined here, and edge inputs are rows of that shape, not
smaller shapes of their own. A new kernel test uses these; a test that
needs another shape says in its docstring what that costs with a cold cache
(ROADMAP Queue 3). tests/test_msm_modes.py::TestKernelShapesPinned fails
when a second shape of an MSM kernel shows up.
"""

import functools
import secrets

from spectre_tpu.fields import bn254 as bn

# The tiny circuit's size: `prover_service/selfverify.py:_tiny_setup`'s, the
# circuit the service proves at start-up. A commitment of that circuit is an
# MSM over 2^TINY_K points, so the tiny proves and the MSM kernel cases meet
# in the same programs (the vanilla kernel's: the mode a served prove runs).
TINY_K = 6
MSM_N = 1 << TINY_K
# The other MSM modes are never proved with (a served prove runs vanilla), so
# their kernels' one shape is free, and small: what a program costs to trace,
# lower and compile grows with the levels of its reduction trees.
MSM_N_OTHER_MODES = 8
# The window each mode's `default_window*` picks at its n (GLV modes see 2n
# points): the (n, c) of the one program a mode's kernel compiles.
MSM_WINDOWS = {"vanilla": 4, "glv": 4, "glv+signed": 5, "fixed": 5}
# the blinding seed of the shared tiny proofs (conftest's `tiny_*_proof`)
TINY_SEED = 0xC0FFEE


def tiny_circuit(cfg):
    """x + x*y = out, x range-checked, one constant pin."""
    n = cfg.n
    x_w, y_w = 7, 3
    out = x_w + x_w * y_w
    advice = [[0] * n for _ in range(cfg.num_advice)]
    advice[0][0], advice[0][1], advice[0][2], advice[0][3] = x_w, x_w, y_w, out
    advice[0][4] = 5
    selectors = [[0] * n for _ in range(cfg.num_advice)]
    selectors[0][0] = 1
    lookup = [[0] * n for _ in range(cfg.num_lookup_advice)]
    lookup[0][0] = x_w
    fixed = [[0] * n for _ in range(cfg.num_fixed)]
    fixed[0][0] = 5
    copies = [
        ((cfg.col_instance(0), 0), (cfg.col_gate_advice(0), 3)),
        ((cfg.col_fixed(0), 0), (cfg.col_gate_advice(0), 4)),
        ((cfg.col_gate_advice(0), 0), (cfg.col_lookup_advice(0), 0)),
    ]
    return advice, lookup, fixed, selectors, copies, out


def tiny_config(k=TINY_K):
    from spectre_tpu.plonk.constraint_system import CircuitConfig
    return CircuitConfig(k=k, num_advice=1, num_lookup_advice=1, num_fixed=1,
                         lookup_bits=4)


def seeded_blinding(seed: int):
    """A zero-argument blinding source that repeats: two proves seeded alike
    give the same bytes."""
    import random
    r = random.Random(seed)
    return lambda: r.randrange(bn.R)


# The inputs of one MSM case, every one as many rows as its mode's shape has:
# what used to be a smaller MSM of its own (all-zero scalars, a single point)
# is a row pattern here.
MSM_CASES = ("random", "all_zero", "one_point", "skewed")


def msm_n(mode) -> int:
    """Rows of a mode's shared shape (None: the default mode, vanilla)."""
    return MSM_N if mode in (None, "vanilla") else MSM_N_OTHER_MODES


@functools.lru_cache(maxsize=1)
def msm_base():
    """MSM_N fixed points with an infinity among the first of them."""
    import random
    rng = random.Random(31)
    pts = [bn.g1_curve.mul(bn.G1_GEN, rng.randrange(bn.R))
           for _ in range(MSM_N)]
    pts[3] = None
    return tuple(pts)


def msm_case(case: str, mode=None, n=None):
    """(points, scalars) of one named case, host values, `n` rows (by
    default those of `mode`'s shape)."""
    n = n or msm_n(mode)
    pts = list(msm_base()[:n])
    if case == "random":          # with the scalars 0, 1 and r - 1 among them
        scalars = [secrets.randbelow(bn.R) for _ in range(n)]
        scalars[:3] = [0, 1, bn.R - 1]
    elif case == "all_zero":      # the identity
        scalars = [0] * n
    elif case == "one_point":     # one term: a scalar multiplication
        scalars = [0] * n
        scalars[n - 2] = secrets.randbelow(bn.R)
    elif case == "skewed":        # every point in one bucket of each window
        scalars = [7] * n
    else:
        raise ValueError(case)
    return pts, scalars


def encode_msm(pts, scalars):
    """Host points and scalars as the device kernels' operands."""
    import jax.numpy as jnp

    from spectre_tpu.ops import ec, limbs as L
    return ec.encode_points(pts), jnp.asarray(L.ints_to_limbs16(scalars))


def check_msm_case(mode, case: str, n=None):
    """One MSM mode (None: the default, SPECTRE_MSM_MODE unset, which is
    vanilla) against the host curve's MSM on one named case."""
    from spectre_tpu.ops import ec, msm as MSM

    pts, scalars = msm_case(case, mode, n)
    want = bn.g1_curve.msm(pts, scalars)
    want = None if want is None else (int(want[0]), int(want[1]))
    got = MSM.msm(*encode_msm(pts, scalars), mode=mode)
    assert ec.decode_points(got[None])[0] == want, (mode, case)
    if case == "all_zero":
        assert want is None


def kernel_programs() -> dict:
    """Programs (one a shape and window) each mode's window-phase kernel
    holds in this process."""
    from spectre_tpu.ops import msm as MSM
    kernels = {"vanilla": MSM.msm_windows, "glv": MSM.msm_windows_bits,
               "glv+signed": MSM.msm_windows_signed,
               "fixed": MSM.msm_fixed_run}
    return {mode: k._cache_size() for mode, k in kernels.items()}
