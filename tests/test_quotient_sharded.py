"""Mesh-sharded quotient pipeline (ISSUE 19, parallel/sharded_quotient.py).

The contract mirrors the mesh-prove identity (tests/test_parallel.py): the
sharded quotient is the SAME computation as the single-device engine in a
different placement — byte-identical h coefficients across every mesh
shape x NTT mode x NTT kernel combination, with the happy path pinned at
ZERO `quotient_sharded_degraded` ticks and the second identical-shape run
pinned at ZERO compiles (the TC-FRESH-JIT runner caches hold).

Inputs are PRODUCTION inputs: a real prove runs once with the host
quotient hooked (the TestDeviceQuotient idiom), so blinds, grand products
and challenges are the ones a prover would see, and the captured host
h coefficients are the oracle for every combo.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.ops import field_ops as F, ntt as NTT
from spectre_tpu.plonk import quotient_device as QD
from spectre_tpu.utils.health import HEALTH

R = bn.R

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 (virtual) devices")
run_slow = pytest.mark.skipif(not os.environ.get("RUN_SLOW"),
                              reason="minutes-scale; set RUN_SLOW=1")


# ---------------------------------------------------------------------------
# production-input capture (one host prove per k, module-cached)
# ---------------------------------------------------------------------------

_CAPTURED: dict = {}


def _capture_quotient_inputs(mk_fixture):
    """Run a CpuBackend prove with `_quotient_host` hooked; return the
    production quotient inputs + the host h-coefficient oracle."""
    import spectre_tpu.plonk.prover as P
    from spectre_tpu.plonk import backend as B
    from spectre_tpu.test_utils import seeded_blinding_rng

    srs, pk, asg = mk_fixture()
    cap = {}
    orig_q = P._quotient_host

    def wrapped(cfg_, dom_, bk_, pk_, polys_, beta, gamma, y):
        h_host = orig_q(cfg_, dom_, bk_, pk_, polys_, beta, gamma, y)

        def fetch(key):
            kind, j = key
            if key in polys_:
                return polys_[key]
            if kind == "shk":
                return pk_.sha_k_poly
            return {"q": pk_.selector_polys, "fix": pk_.fixed_polys,
                    "sig": pk_.sigma_polys, "tab": pk_.table_polys,
                    "shq": pk_.sha_selector_polys}[kind][j]

        cap.update(cfg=cfg_, dom=dom_, fetch=fetch, beta=beta,
                   gamma=gamma, y=y, h_host=h_host)
        return h_host

    P._quotient_host = wrapped
    try:
        P.prove(pk, srs, asg, B.CpuBackend(),
                blinding_rng=seeded_blinding_rng())
    finally:
        P._quotient_host = orig_q
    assert cap, "prove never reached the quotient phase"
    return cap


def _captured_k6():
    """k=6 gate+lookup circuit: n_ext = 256, Bailey 16x16 — divisible by
    every mesh shape in the identity matrix. Captured once per session."""
    if 6 not in _CAPTURED:
        def mk():
            from spectre_tpu.builder import RangeChip
            from spectre_tpu.builder.context import Context
            from spectre_tpu.plonk import backend as B
            from spectre_tpu.plonk.keygen import keygen
            from spectre_tpu.plonk.srs import SRS

            ctx = Context()
            rng = RangeChip(lookup_bits=4)
            g = rng.gate
            a = ctx.load_witness(5)
            b = ctx.load_witness(9)
            c = g.mul(ctx, a, b)
            rng.range_check(ctx, a, 4)
            ctx.expose_public(c)
            cfg = ctx.auto_config(k=6, lookup_bits=4)
            asg = ctx.assignment(cfg)
            srs = SRS.unsafe_setup(8)
            pk = keygen(srs, cfg, asg.fixed, asg.selectors, asg.copies,
                        B.CpuBackend())
            return srs, pk, asg

        _CAPTURED[6] = _capture_quotient_inputs(mk)
    return _CAPTURED[6]


def _captured_k11():
    if 11 not in _CAPTURED:
        from spectre_tpu.test_utils import mesh_prove_fixture
        _CAPTURED[11] = _capture_quotient_inputs(
            lambda: mesh_prove_fixture(k=11))
    return _CAPTURED[11]


def _run_quotient(cap):
    return QD.compute_quotient(cap["cfg"], cap["dom"], cap["fetch"],
                               cap["beta"], cap["gamma"], cap["y"])


# ---------------------------------------------------------------------------
# the identity matrix
# ---------------------------------------------------------------------------

# Tier-1 keeps a representative slice of the shape x mode x kernel matrix
# (the verify budget is shared by the whole suite): every mesh shape on the
# default (radix2, stages) pair, plus both fourstep kernels on the full 8-way
# mesh. The remaining combos run under RUN_SLOW (the matmul kernel is a no-op
# under radix2, and the 1x1/2x1 fourstep arms re-prove what 4x2 proves on a
# smaller permutation group).
_TIER1_COMBOS = [
    ("1x1", "radix2", "stages"),
    ("2x1", "radix2", "stages"),
    ("4x2", "radix2", "stages"),
    ("4x2", "fourstep", "stages"),
    ("4x2", "fourstep", "matmul"),
]
_SLOW_COMBOS = [
    (shape, mode, kernel)
    for shape in ("1x1", "2x1", "4x2")
    for mode in ("radix2", "fourstep")
    for kernel in ("stages", "matmul")
    if (shape, mode, kernel) not in _TIER1_COMBOS
]


@needs8
class TestShardedQuotientIdentity:
    """mesh shape x NTT mode x NTT kernel: byte-identical h coefficients,
    zero degrades. 1x1 is the single-device arm of the identity (the mesh
    gate disengages at one device — that IS the reference path)."""

    @pytest.mark.parametrize("mesh_shape,ntt_mode,ntt_kernel", _TIER1_COMBOS)
    def test_identity_matrix_k6(self, monkeypatch, mesh_shape, ntt_mode,
                                ntt_kernel):
        cap = _captured_k6()
        monkeypatch.setenv("SPECTRE_SHARD_QUOTIENT_MIN_LOGN", "0")
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", mesh_shape)
        monkeypatch.setenv("SPECTRE_NTT_MODE", ntt_mode)
        monkeypatch.setenv("SPECTRE_NTT_KERNEL", ntt_kernel)
        before = HEALTH.get("quotient_sharded_degraded")
        h = _run_quotient(cap)
        assert np.array_equal(h, cap["h_host"]), \
            f"h bytes diverge on {mesh_shape} / {ntt_mode} / {ntt_kernel}"
        assert HEALTH.get("quotient_sharded_degraded") == before, \
            "sharded quotient degraded on an eligible shape"

    @run_slow
    @pytest.mark.parametrize("mesh_shape,ntt_mode,ntt_kernel", _SLOW_COMBOS)
    def test_identity_matrix_k6_full(self, monkeypatch, mesh_shape, ntt_mode,
                                     ntt_kernel):
        self.test_identity_matrix_k6(monkeypatch, mesh_shape, ntt_mode,
                                     ntt_kernel)

    def test_second_identical_run_pins_zero_compiles(self, monkeypatch):
        """The TC-FRESH-JIT contract end-to-end: after one warm pass on a
        shape, a second identical-shape quotient compiles NOTHING — every
        eval/roll/LDE/inverse runner comes out of its plan-keyed cache."""
        from spectre_tpu.observability import compilelog

        cap = _captured_k6()
        monkeypatch.setenv("SPECTRE_SHARD_QUOTIENT_MIN_LOGN", "0")
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "4x2")
        compilelog.install()
        _run_quotient(cap)                       # warm
        with compilelog.capture() as events:
            h = _run_quotient(cap)
        assert np.array_equal(h, cap["h_host"])
        comp = compilelog.summarize(events)
        assert comp["count"] == 0, \
            f"second identical-shape quotient recompiled: {comp}"


@needs8
def test_mesh_lde_splits_the_packed_stack_where_it_lies(monkeypatch):
    """ISSUE 39: the mesh engine's stack goes up packed and batch-sharded;
    each device splits its own columns' limbs and pads their rows, so the
    split program holds no collective and its output has the stack's
    sharding (what `_lde_runner` takes)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spectre_tpu.ops import limbs as L16
    from spectre_tpu.parallel.plan import current_plan

    monkeypatch.setenv("SPECTRE_MESH_SHAPE", "4x2")
    plan = current_plan()
    sh = NamedSharding(plan.batch_mesh, P(plan.batch_axis, None, None))
    rows = np.random.default_rng(39).integers(
        0, 2**64, (8, 16, 4), dtype=np.uint64)
    packed = jax.device_put(L16.pack_u64limbs(rows), sh)
    hlo = L16.split_limbs16.lower(packed, 64).compile().as_text()
    for word in ("all-gather", "all-to-all", "all-reduce",
                 "collective-permute"):
        assert word not in hlo, word
    got = L16.split_limbs16(packed, 64)
    assert got.sharding.is_equivalent_to(sh, 3)
    want = np.zeros((8, 64, 16), dtype=np.uint32)
    want[:, :16] = L16.u64limbs_to_u16limbs(
        rows.reshape(-1, 4)).reshape(8, 16, 16)
    assert np.array_equal(np.asarray(got), want)


@needs8
@run_slow
class TestShardedQuotientK11:
    """The k=11 arm (n_ext = 2^13 — above the default size
    gate, so this also exercises the production gate path untouched)."""

    def test_mesh_byte_identity_k11(self, monkeypatch):
        cap = _captured_k11()
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "4x2")
        before = HEALTH.get("quotient_sharded_degraded")
        h = _run_quotient(cap)
        assert np.array_equal(h, cap["h_host"])
        assert HEALTH.get("quotient_sharded_degraded") == before


# ---------------------------------------------------------------------------
# dispatch: gates, kill switch, eligibility, visible degrade
# ---------------------------------------------------------------------------

@needs8
class TestShardedDispatch:
    def test_eligibility(self, monkeypatch):
        from spectre_tpu.parallel import sharded_quotient as SQ
        from spectre_tpu.parallel.plan import current_plan

        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "4x2")
        plan = current_plan()
        assert plan.n_devices == 8
        assert SQ.eligible(plan, 256)       # Bailey 16x16: 8 | 16
        assert SQ.eligible(plan, 1 << 13)
        assert not SQ.eligible(plan, 16)    # Bailey 4x4: 8 does not divide
        assert not SQ.eligible(plan, 192)   # not a power of two

    def test_silent_below_gate_and_kill_switch(self, monkeypatch):
        cap = _captured_k6()
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "4x2")
        before = HEALTH.get("quotient_sharded_degraded")
        # below the size gate (default 18 > logm=8): silently single-device
        monkeypatch.delenv("SPECTRE_SHARD_QUOTIENT_MIN_LOGN", raising=False)
        assert QD._mesh_engine(cap["dom"]) is None
        # kill switch: silently single-device even above the gate
        monkeypatch.setenv("SPECTRE_SHARD_QUOTIENT_MIN_LOGN", "0")
        monkeypatch.setenv("SPECTRE_QUOTIENT_SHARDED", "0")
        assert QD._mesh_engine(cap["dom"]) is None
        assert HEALTH.get("quotient_sharded_degraded") == before

    def test_ineligible_above_gate_degrades_visibly(self, monkeypatch):
        from spectre_tpu.plonk.domain import Domain

        monkeypatch.setenv("SPECTRE_SHARD_QUOTIENT_MIN_LOGN", "0")
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "4x2")
        before = HEALTH.get("quotient_sharded_degraded")
        # k=2 -> n_ext=16, Bailey 4x4: an 8-way mesh cannot cover it
        assert QD._mesh_engine(Domain(2)) is None
        assert HEALTH.get("quotient_sharded_degraded") == before + 1

    def test_mesh_exception_falls_back_visibly_and_correctly(
            self, monkeypatch):
        """A mesh-path failure mid-quotient must fall back to the local
        engine with the SAME bytes — and tick the degrade counter, never
        silently."""
        from spectre_tpu.parallel import sharded_quotient as SQ

        cap = _captured_k6()
        monkeypatch.setenv("SPECTRE_SHARD_QUOTIENT_MIN_LOGN", "0")
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "4x2")

        def boom(self, std16):
            raise RuntimeError("injected mesh failure")

        monkeypatch.setattr(SQ.MeshQuotientEngine, "lde", boom)
        before = HEALTH.get("quotient_sharded_degraded")
        h = _run_quotient(cap)
        assert np.array_equal(h, cap["h_host"])
        assert HEALTH.get("quotient_sharded_degraded") == before + 1


# ---------------------------------------------------------------------------
# quotient scalar cache (_TableLRU, ISSUE 19 satellite)
# ---------------------------------------------------------------------------

class TestScalarLRU:
    def test_recompute_after_eviction_is_counted(self):
        from spectre_tpu.ops.msm import _TableLRU

        lru = _TableLRU(4 * 64, label="test scalars")   # four [16] u32 rows
        mk = lambda v: np.full(16, v, np.uint32)
        for v in range(4):
            lru.put(v, None, mk(v))
        assert lru.stats()["evictions"] == 0
        lru.put(4, None, mk(4))                  # evicts the oldest (0)
        assert lru.get(0, None) is None
        lru.put(0, None, mk(0))                  # the rebuild IS a recompute
        st = lru.stats()
        assert st["evictions"] >= 1
        assert st["recomputes"] == 1
        assert st["entries"] == 4

    def test_quotient_exact_under_tiny_budget(self, monkeypatch):
        """Eviction costs recompute time, never correctness: a 2-entry
        scalar budget thrashes (recomputes > 0 in stats) but the h bytes
        stay identical to the uncached-oracle run."""
        from spectre_tpu.ops.msm import _TableLRU

        cap = _captured_k6()
        tiny = _TableLRU(128, label="quotient mont scalar",
                         budget_var="SPECTRE_QUOTIENT_SCALAR_MB")
        monkeypatch.setattr(QD, "_scalar_cache", tiny)
        h = _run_quotient(cap)
        assert np.array_equal(h, cap["h_host"])
        st = tiny.stats()
        assert st["evictions"] > 0
        assert st["recomputes"] > 0, \
            "y re-enters every fold: a 2-entry budget must show recomputes"

    def test_stats_exported(self):
        st = QD.scalar_lru_stats()
        for key in ("hits", "builds", "evictions", "recomputes", "bytes",
                    "budget_bytes", "entries"):
            assert key in st


# ---------------------------------------------------------------------------
# _MATMUL_MAX_LOGN boundary (the cap the sharded inverse legs ride)
# ---------------------------------------------------------------------------

def _poly(n, seed=23):
    return [(i * 2654435761 + seed) % R for i in range(n)]


def _mont(vals):
    return jnp.asarray(F.fr_ctx().encode_np(vals))


class TestMatmulCapBoundary:
    def test_grouped_split_matches_stages(self):
        """The two-level carry split (the mechanism that lifted the cap to
        12) forced onto a small transform: group_width=2 at n=64 runs 16
        groups through per-group carry + group-sum + renormalize, and must
        be byte-identical to the butterfly stages AND to the unsplit
        single-matmul collapse."""
        omega = bn.fr_root_of_unity(6)
        a = _mont(_poly(64, seed=17))
        want = np.asarray(NTT._ntt_stages(a, 6, omega))
        grouped = np.asarray(NTT._ntt_dft_matmul(a, 6, omega, group_width=2))
        unsplit = np.asarray(NTT._ntt_dft_matmul(a, 6, omega))
        assert np.array_equal(want, grouped)
        assert np.array_equal(want, unsplit)

    @pytest.mark.slow
    def test_cap_boundary_full_length(self):
        """n = 2^_MATMUL_MAX_LOGN — the longest transform the exactness
        proof (kernel_lint.lint_matmul_cap) admits — against the stages
        oracle at the REAL production group width."""
        logn = NTT._MATMUL_MAX_LOGN
        assert logn >= 12, "ISSUE 19: the cap must cover n_ext legs to 2^24"
        assert NTT._conv_group_width(logn) < 32, \
            "the boundary length must exercise the grouped path"
        omega = bn.fr_root_of_unity(logn)
        a = _mont(_poly(1 << logn, seed=29))
        got = np.asarray(NTT._ntt_dft_matmul(a, logn, omega))
        want = np.asarray(NTT._ntt_stages(a, logn, omega))
        assert np.array_equal(got, want)
