"""One explicit ShardingPlan for the mesh prove path (ISSUE 13 tentpole).

Before this module existed every mesh call site improvised: `sharded_msm`
re-built (and re-jit) a fresh shard_map closure per call, `sharded_ntt`
re-transferred its twiddle matrix and re-jit per call, and the backend
re-expanded/re-placed the commitment base per MSM. On the 1-core
8-virtual-device box that meant a FULL 8-way SPMD retrace + lowering for
every one of the ~20 MSMs and ~40 NTTs in a k=13 prove — the recorded
MULTICHIP_r01/r05 rc=124 timeouts. SZKP (arXiv:2408.05890) and "Enabling
AI ASICs for ZKP" (arXiv:2604.17808) both make the same point from the
hardware side: the mesh kernels only win once data placement is explicit
and the SPMD program build is hoisted out of the hot path.

The ShardingPlan is that explicit placement contract:

  * mesh axes + shape      — ("data", "win"), honoring SPECTRE_MESH_SHAPE
  * point/scalar placement — rows sharded along "data" (pad_rows pads so
                             the axis divides evenly)
  * window placement       — Pippenger windows sharded along "win"
                             (pad_windows)
  * fixed-base tables      — [nwin, N, 3, 16] window tables sharded along
                             the ROW axis (`table_spec`): each data shard
                             holds exactly the T[w] row slices for its
                             point shard (co-resident, no re-transfer)
  * signed-digit recode    — per shard (each shard holds whole scalars,
                             so the carry scan never crosses a boundary)
  * NTT row/col split      — `ntt_split(logn)` picks the Bailey split the
                             data axis divides

Every consumer caches its compiled SPMD program keyed by `plan.key` (plus
its own static params): one jit per (plan, shape-class), not per call.
`plan_for_mesh` interns plans so the mesh object captured by those cached
closures stays alive and stable.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import default_mesh

DATA_AXIS = "data"
WIN_AXIS = "win"


@dataclasses.dataclass(frozen=True, eq=False)
class ShardingPlan:
    """Placement contract for one ("data", "win") device mesh.

    Immutable and interned per device-set (`plan_for_mesh`): consumers key
    their compiled-program caches on `plan.key` and capture `plan.mesh`
    in shard_map closures, so two calls under the same plan always reuse
    the same trace."""

    mesh: Mesh
    data_axis: str = DATA_AXIS
    win_axis: str = WIN_AXIS
    # signed-digit recode runs inside each data shard (whole scalars per
    # row -> the carry scan is shard-local); documented here because the
    # runner builders branch on it when composing kernels
    per_shard_recode: bool = True

    # -- shape --

    @property
    def ndata(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def nwin_shards(self) -> int:
        return self.mesh.shape.get(self.win_axis, 1)

    @property
    def n_devices(self) -> int:
        return self.ndata * self.nwin_shards

    @functools.cached_property
    def key(self) -> tuple:
        """Hashable identity for compiled-program caches."""
        return (tuple(d.id for d in self.mesh.devices.flat),
                self.ndata, self.nwin_shards,
                self.data_axis, self.win_axis)

    # -- placements (PartitionSpecs over the mesh) --

    @property
    def point_spec(self) -> P:
        """[n, 3, 16] projective points: rows along the data axis."""
        return P(self.data_axis, None, None)

    @property
    def scalar_spec(self) -> P:
        """[n, L] limb scalars: rows along the data axis."""
        return P(self.data_axis, None)

    @property
    def sign_spec(self) -> P:
        """[n] bool sign masks: along the data axis."""
        return P(self.data_axis,)

    @property
    def table_spec(self) -> P:
        """[nwin, N, 3, 16] fixed-base window table: ROW axis along
        "data" — T[w] slices co-resident with their point shards; the
        window axis stays whole (each win shard dynamic-slices its
        windows locally)."""
        return P(None, self.data_axis, None, None)

    @property
    def ntt_spec(self) -> P:
        """[rows, cols, 16] Bailey matrix: rows along the data axis."""
        return P(self.data_axis, None, None)

    def replicated(self, ndim: int) -> P:
        return P(*([None] * ndim))

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def place(self, arr, spec: P):
        """device_put onto the mesh with the given placement."""
        return jax.device_put(arr, self.sharding(spec))

    # -- padding --

    def pad_rows(self, n: int) -> int:
        """Rows padded so the data axis divides evenly (pad points with
        infinity / scalars with zero — identity contributions)."""
        d = self.ndata
        return ((n + d - 1) // d) * d

    def pad_windows(self, nwin: int) -> int:
        """Window count padded so the win axis divides evenly (padded
        windows read digits beyond nbits — always zero, harmless)."""
        s = self.nwin_shards
        return ((nwin + s - 1) // s) * s

    # -- NTT decomposition --

    def ntt_split(self, logn: int) -> tuple[int, int]:
        """(logr, logc) Bailey split for a 2^logn NTT such that the data
        axis divides both matrix dims; raises when the transform is too
        small for this mesh."""
        logs = (self.ndata - 1).bit_length()
        logr = logn // 2
        logc = logn - logr
        if logr < logs or logc < logs:
            raise ValueError(
                f"2^{logn} NTT cannot split across a {self.ndata}-way data "
                f"axis (needs 2^{2 * logs} rows minimum)")
        return logr, logc

    # -- batch (DP) axis --

    @functools.cached_property
    def batch_mesh(self) -> Mesh:
        """1-D ("batch",) mesh over the same device set, for the
        inter-proof / multi-column DP axis (parallel.batch_msm)."""
        return Mesh(self.mesh.devices.reshape(-1), (self.batch_axis,))

    @property
    def batch_axis(self) -> str:
        return "batch"

    # -- introspection (manifests) --

    def describe(self) -> dict:
        return {
            "mesh": dict(self.mesh.shape),
            "n_devices": self.n_devices,
            "points": f"rows over '{self.data_axis}'",
            "windows": f"over '{self.win_axis}'",
            "fixed_table": f"T[w] rows over '{self.data_axis}' "
                           f"(co-resident with point shards)",
            "recode": "per-shard signed-digit"
                      if self.per_shard_recode else "host",
            "ntt": f"Bailey row/col, rows over '{self.data_axis}', "
                   f"transpose = all_to_all",
        }


# interned plans: the mesh object held here is the one captured by every
# cached shard_map closure, so plan identity == program-cache validity
_PLANS: dict = {}


def plan_for_mesh(mesh: Mesh, data_axis: str = DATA_AXIS,
                  win_axis: str = WIN_AXIS) -> ShardingPlan:
    """Interned ShardingPlan for a mesh (same device set + axes -> the
    SAME plan object, holding the first mesh seen)."""
    axes = tuple(mesh.axis_names)
    if data_axis not in axes:
        # 1-D meshes (tests, the batch path) get a degenerate win axis
        data_axis = axes[0]
        win_axis = axes[1] if len(axes) > 1 else win_axis
    key = (tuple(d.id for d in mesh.devices.flat),
           tuple(mesh.shape.items()), data_axis, win_axis)
    plan = _PLANS.get(key)
    if plan is None:
        if len(_PLANS) > 16:
            _PLANS.clear()
        plan = ShardingPlan(mesh=mesh, data_axis=data_axis,
                            win_axis=win_axis)
        _PLANS[key] = plan
    return plan


def current_plan() -> ShardingPlan:
    """The process-default plan: `default_mesh()` (all local devices,
    honoring SPECTRE_MESH_SHAPE) interned through `plan_for_mesh`."""
    return plan_for_mesh(default_mesh())


# ---------------------------------------------------------------------------
# runner-registry contract (trace-cache hygiene)
# ---------------------------------------------------------------------------
# Every module that builds jitted/SPMD programs against a plan keys its
# compiled-program cache on `plan.key` + its own statics, and DECLARES the
# (builder, cache-dict) pairs in a module-level `TRACE_RUNNER_CACHES`
# tuple (modules whose jitted entry points live at module level declare a
# `TRACE_JIT_ROOTS` name tuple instead). The declarations are read by
# `spectre_tpu.analysis.trace_lint` via AST — no imports, so ops/ modules
# never grow an import edge into parallel/ — which flags undeclared or
# stale entries (TC-UNCACHED-RUNNER) and dynamically double-calls the
# registered runners asserting zero recompiles (TC-RETRACE-DYN).

# modules participating in the runner-registry contract
RUNNER_REGISTRY_MODULES = (
    "spectre_tpu.parallel.sharded_msm",
    "spectre_tpu.parallel.sharded_ntt",
    "spectre_tpu.parallel.sharded_quotient",
    "spectre_tpu.parallel.batch_msm",
    "spectre_tpu.plonk.quotient_device",
    "spectre_tpu.plonk.backend",
)


def runner_registry() -> dict:
    """{module name -> declared (builder, cache) pairs} — the live-import
    view of the contract (tests pin it against the AST view)."""
    import importlib

    out = {}
    for name in RUNNER_REGISTRY_MODULES:
        m = importlib.import_module(name)
        out[name] = tuple(getattr(m, "TRACE_RUNNER_CACHES", ()))
    return out
