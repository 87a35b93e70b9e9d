"""Will the main prove path compile for the chip? Asked of the TPU compiler
itself, for a DESCRIBED v5e:2x2 — no chip attached, nothing runs.

The shapes are the smoke's (committee-update Minimal-32, k=14: columns of
2^14 rows, the 2^16 extended domain) and production's (2^18). A compile that
passes is not a chip run: it says the program lowers, fits, and which
collectives the partitioner put in. The slow ones (msm_windows 2^14/2^18/2^21:
minutes each) are run by hand and recorded in CHANGES.md / PERF.md instead.

The topology is described inside a module-scoped fixture: only one process at
a time may load libtpu, and xdist workers each import every test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HBM_BYTES = 16 << 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_plan(topo):
    from jax.sharding import Mesh

    from spectre_tpu.parallel.plan import plan_for_mesh
    return plan_for_mesh(Mesh(np.array(topo.devices).reshape(2, 2),
                              ("data", "win")))


@pytest.fixture(scope="module", autouse=True)
def _chip_branches_no_persistent_cache():
    """Two things the program would see differently on the chip, steered
    here and not through an option of the program: the persistent compile
    cache is off (a described-topology entry can be written but never read
    back), and `jax.default_backend()` answers "tpu" so trace-time platform
    branches (`ops/ntt.py:_batch_rows`) lower the layout the chip runs."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    yield
    jax.default_backend = real
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(shape, sharding, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes)
    assert per_device < HBM_BYTES, f"{per_device} bytes on one 16 GB chip"
    return compiled


class TestOneChip:
    def test_combine_windows_k14(self, one_chip):
        from spectre_tpu.ops import msm as MSM
        c = MSM.default_window(1 << 14)
        nwin = (254 + c - 1) // c
        _compile(MSM.combine_windows, _shape((nwin, 3, 16), one_chip), c)

    @pytest.mark.parametrize("logn", [14, 18])
    def test_chunk_combine_and_affine(self, one_chip, logn):
        """What a run of the one-chip commit path adds to a column's window
        phase: the combine chain and the affine conversion at CHUNK_WIDTH."""
        from spectre_tpu.ops import ec, msm as MSM
        c = MSM.default_window(1 << logn)
        nwin = (254 + c - 1) // c
        _compile(MSM.combine_windows_batch,
                 _shape((MSM.CHUNK_WIDTH, nwin, 3, 16), one_chip), c)
        _compile(ec._affine_mont, _shape((MSM.CHUNK_WIDTH, 3, 16), one_chip))

    @pytest.mark.parametrize("logn", [14, 18])
    def test_ntt_forward(self, one_chip, logn):
        from spectre_tpu.ops import ntt as NTT
        from spectre_tpu.plonk.domain import Domain
        mode = NTT._resolve_mode(None, logn)
        _compile(NTT._fwd_kernel, _shape((1 << logn, 16), one_chip),
                 Domain(logn).omega, None, mode,
                 NTT._resolve_kernel(None, mode))

    def test_ntt_inverse_2_18(self, one_chip):
        from spectre_tpu.ops import ntt as NTT
        from spectre_tpu.plonk.domain import Domain
        mode = NTT._resolve_mode(None, 18)
        _compile(NTT._inv_kernel, _shape((1 << 18, 16), one_chip),
                 Domain(18).omega, None, False, mode,
                 NTT._resolve_kernel(None, mode), None)

    def test_batched_coset_lde_k14(self, one_chip):
        """The quotient's prefetch: [B, 4n, 16] standard-form columns, the
        vectorised (non-CPU) batch layout."""
        from spectre_tpu.ops import ntt as NTT
        from spectre_tpu.plonk.domain import COSET_GEN, Domain
        from spectre_tpu.plonk.quotient_device import _ext_chunk
        dom = Domain(14)
        logm = dom.n_ext.bit_length() - 1
        mode = NTT._resolve_mode(None, logm)
        _compile(NTT._fwd_kernel,
                 _shape((_ext_chunk(dom.n_ext), dom.n_ext, 16), one_chip),
                 dom.omega_ext, ("std", COSET_GEN), mode,
                 NTT._resolve_kernel(None, mode))

    def test_packed_columns_split_and_padded_k14(self, one_chip):
        """ISSUE 39: a chunk of the quotient's columns arrives as [B, n, 8]
        packed rows; the device splits the limbs and pads to the extended
        domain, the shape `test_batched_coset_lde_k14`'s program takes."""
        from spectre_tpu.ops import limbs as L16
        from spectre_tpu.plonk.domain import Domain
        from spectre_tpu.plonk.quotient_device import _ext_chunk
        dom = Domain(14)
        b = _ext_chunk(dom.n_ext)
        compiled = _compile(L16.split_limbs16,
                            _shape((b, dom.n, 8), one_chip), dom.n_ext)
        (out,) = jax.tree.leaves(compiled.out_info)
        assert out.shape == (b, dom.n_ext, 16) and out.dtype == jnp.uint32

    def test_quotient_fold_runner_k14(self, one_chip):
        from spectre_tpu.plonk import quotient_device as QD
        from spectre_tpu.plonk.domain import Domain
        m = Domain(14).n_ext
        _compile(QD._helpers()["fold"], _shape((m, 16), one_chip),
                 _shape((16,), one_chip), _shape((m, 16), one_chip))


class TestFourChipMesh:
    def test_sharded_ntt_2_18(self, mesh_plan):
        # (the package re-exports the FUNCTION under the module's name)
        import importlib

        from jax.sharding import PartitionSpec as P
        SN = importlib.import_module("spectre_tpu.parallel.sharded_ntt")
        from spectre_tpu.plonk.domain import Domain
        logn = 18
        rr, cc = 1 << (logn // 2), 1 << (logn - logn // 2)
        sh = mesh_plan.sharding(P("data", None, None))
        compiled = _compile(
            SN._ntt_runner(mesh_plan, "data", logn, Domain(logn).omega),
            _shape((rr, cc, 16), sh), _shape((rr, cc, 16), sh))
        assert "all-to-all" in compiled.as_text()

    def test_packed_columns_split_where_they_lie_k14(self, mesh_plan):
        """The mesh engine's stack goes up batch-sharded and packed; the
        split and the padding cross no device boundary."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from spectre_tpu.ops import limbs as L16
        from spectre_tpu.plonk.domain import Domain
        dom = Domain(14)
        sh = NamedSharding(mesh_plan.batch_mesh, P("batch", None, None))
        hlo = _compile(L16.split_limbs16, _shape((8, dom.n, 8), sh),
                       dom.n_ext).as_text()
        for word in ("all-gather", "all-to-all", "all-reduce",
                     "collective-permute"):
            assert word not in hlo, word

    def test_sharded_quotient_roll_k14(self, mesh_plan):
        """Rotation of a row-sharded extended column: the ppermute halo
        exchange on the flat 4-device batch mesh."""
        import importlib

        from jax.sharding import NamedSharding, PartitionSpec as P
        SQ = importlib.import_module("spectre_tpu.parallel.sharded_quotient")
        from spectre_tpu.plonk.domain import Domain
        m = Domain(14).n_ext
        row = NamedSharding(mesh_plan.batch_mesh, P("batch", None))
        compiled = _compile(SQ._roll_runner(mesh_plan, m, 4),
                            _shape((m, 16), row))
        assert "collective-permute" in compiled.as_text()
