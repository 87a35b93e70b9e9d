"""Everything that needs a whole keygen and prove of the tiny circuit on
`TpuBackend` (XLA:CPU here), in ONE file: under `--dist loadfile` a file is
one worker's, so the device programs of that prove (the MSM window phase,
the transforms, the quotient's, the mesh's data-parallel MSM) are traced,
lowered and compiled once for all of these and not once a file.

- the one-chip plan's prove under a trace (`tiny_tpu_prove`): its bytes
  against `CpuBackend`'s, its spans at the device boundary (ISSUE 29; no
  test here asserts a time: names, order, containment and counts only);
- the default plan's (8 virtual devices) keygen and prove, bytes against
  the same;
- the second NTT mode's keygen and prove, bytes against the first;
- the one-chip commit path's runs of columns (ISSUE 30), which are the same
  calls the spans look at;
- first in the file, the default MSM mode's kernel cases: the window-phase
  program every prove above commits with.
"""

import collections
import re
import types

import numpy as np
import pytest

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.native import host
from spectre_tpu.observability import tracing
from spectre_tpu.plonk import backend as B
from spectre_tpu.plonk.keygen import keygen
from spectre_tpu.plonk.prover import prove
from spectre_tpu.plonk.verifier import verify

from _shapes import (MSM_CASES, MSM_N, MSM_WINDOWS, TINY_SEED,
                     check_msm_case, encode_msm, kernel_programs, msm_case,
                     seeded_blinding)


class TestDefaultMsm:
    """The default MSM (no mode named: vanilla) against the host curve's,
    every case a row pattern of the ONE shared shape (tests/_shapes.py):
    the window-phase program of the tiny prove below, compiled here first.
    The other modes are tests/test_msm_modes.py's."""

    @pytest.fixture(scope="class", autouse=True)
    def programs_before(self):
        return kernel_programs()["vanilla"]

    @pytest.mark.parametrize("case", MSM_CASES)
    def test_matches_oracle(self, case, monkeypatch):
        monkeypatch.delenv("SPECTRE_MSM_MODE", raising=False)
        check_msm_case(None, case)

    def test_one_program(self, programs_before):
        """A case that brings another (n, c) of the kernel fails here
        (tests/_shapes.py says why)."""
        assert kernel_programs()["vanilla"] - programs_before <= 1


class TestActiveWindows:
    """ISSUE 38: `msm_windows` runs the low `active` windows, a count that
    is DATA (`np.int32`), so every case here is the one program above, the
    served one. A column whose largest scalar is under, or AT, the bound
    2^(c * active) - 1 gives the host curve's point; `windows_needed` reads
    that count off the host column."""

    C = MSM_WINDOWS["vanilla"]
    FULL = -(-254 // MSM_WINDOWS["vanilla"])

    @pytest.fixture(scope="class", autouse=True)
    def programs_before(self):
        return kernel_programs()["vanilla"]

    def _check(self, scalars, active):
        import jax.numpy as jnp

        from spectre_tpu.ops import ec, limbs as L16, msm as MSM

        pts = list(msm_case("random")[0])
        want = bn.g1_curve.msm(pts, scalars)
        want = None if want is None else (int(want[0]), int(want[1]))
        assert MSM.windows_needed(B.to_arr(scalars), self.C) == active
        wins = MSM.msm_windows(
            ec.encode_points(pts), jnp.asarray(L16.ints_to_limbs16(scalars)),
            self.C, np.int32(active))
        assert wins.shape[0] == self.FULL
        # the windows that did not run are the identity (z = 0)
        assert not np.asarray(wins[active:, 2]).any()
        assert ec.decode_points(MSM.combine_windows(wins, self.C)[None])[0] \
            == want
        return wins

    @pytest.mark.parametrize("active,where", [
        (0, "under"), (1, "under"), (1, "at"), (2, "under"), (2, "at"),
        (FULL, "under"), (FULL, "at")])
    def test_matches_oracle(self, active, where):
        import random
        rng = random.Random(38 + active)
        top = min(1 << (self.C * active), bn.R) - 1     # the bound itself
        low = 1 << (self.C * (active - 1)) if active else 0
        scalars = [rng.randrange(low, top + 1) if top else 0
                   for _ in range(MSM_N)]
        scalars[:2] = [0, low]
        if where == "at":
            scalars[5] = top
        self._check(scalars, active)

    def test_all_windows_bit_equal_to_the_static_loop(self):
        """The full-width call against the form every window ran in until
        ISSUE 38 (`active` None: a counted loop over all of them, what the
        mesh kernels still trace inside their own programs): the same
        limbs, not just the same point. The one other program of this
        (n, c) in tier-1: some tens of seconds with a cold compile cache."""
        import jax.numpy as jnp

        from spectre_tpu.ops import msm as MSM

        pp, sc = encode_msm(*msm_case("random"))
        assert jnp.array_equal(
            MSM.msm_windows(pp, sc, self.C),
            MSM.msm_windows(pp, sc, self.C, np.int32(self.FULL)))

    def test_one_program_for_every_count(self, programs_before):
        # the counts above and the default MSM's share one (compiled here
        # where this class runs alone); the static loop's is the other
        assert kernel_programs()["vanilla"] - programs_before <= 2


class TestChunkCombine:
    def test_matches_single(self):
        """The one-chip commit path's shape: a window phase a column, then
        the combine at a fixed width with identity window sums for the
        missing columns; the points are the single MSM's."""
        from spectre_tpu.ops import ec, msm as MSM

        m, width, c = 3, 8, MSM_WINDOWS["vanilla"]
        operands = [encode_msm(*msm_case("random")) for _ in range(m)]
        full = np.int32(MSM.window_count(254, c))
        wins = tuple(MSM.msm_windows(pp, sc, c, full) for pp, sc in operands)
        want = [ec.decode_points(MSM.combine_windows(w, c)[None])[0]
                for w in wins]
        padded = MSM.pad_window_sums(wins, width)
        assert padded.shape == (width,) + wins[0].shape
        assert ec.decode_points(MSM.combine_windows_batch(padded, c)) \
            == want + [None] * (width - m)
        assert MSM.pad_window_sums(wins, m).shape[0] == m


def _tpu_keygen(tiny):
    """(pk, backend): the tiny circuit's key through `TpuBackend` (XLA:CPU
    here), under the plan and modes the environment names."""
    bk = B.get_backend("tpu")
    return keygen(tiny.srs, tiny.cfg, tiny.fixed, tiny.selectors, tiny.copies,
                  bk), bk


def _seeded_prove(tiny, pk, bk):
    """A prove seeded as `tiny_cpu_proof` is."""
    return prove(pk, tiny.srs, tiny.asg, bk,
                 blinding_rng=seeded_blinding(TINY_SEED))


@pytest.fixture(scope="module")
def tiny_tpu_prove(tiny):
    """Keygen and one prove on a 1x1 mesh, as the one-chip cell runs them,
    the prove under a trace."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SPECTRE_MESH_SHAPE", "1x1")
        pk, bk = _tpu_keygen(tiny)
        with tracing.trace("tiny-tpu-prove") as tr:
            proof = _seeded_prove(tiny, pk, bk)
    return types.SimpleNamespace(trace=tr, proof=proof, pk=pk, srs=tiny.srs,
                                 bk=bk)


# ---------------------------------------------------------------------------
# spans at the device boundary (ISSUE 29). No test here asserts a time:
# names, order, containment and counts only.

PROVE_PHASES = {
    "prove/aggregation", "prove/app_snark", "prove/commit_advice",
    "prove/commit_h", "prove/cross_verify", "prove/evals",
    "prove/grand_products", "prove/instance_polys", "prove/lookup_permute",
    "prove/multiopen", "prove/quotient", "prove/self_verify"}
# the stages of one call, in order, by the last segment of their names
MSM_STAGES = ["encode", "dispatch", "dispatch", "wait", "decode"]
# the batched mesh branch reads the mesh result whole and sends it back up
MESH_MSM_STAGES = ["encode", "dispatch", "wait", "dispatch", "wait", "decode"]
# one device, default mode: a run's window phases, its combine and its affine
# conversion are enqueued together and read once (`TpuBackend._msm_chunks`)
CHUNK_MSM_STAGES = ["encode", "dispatch", "wait", "decode"]
MSM_WIDTH = 16       # `ops/msm.py:CHUNK_WIDTH`
TWO_CROSSINGS = ["encode", "dispatch", "wait",
                 "encode", "dispatch", "wait", "decode"]


def _walk(s):
    yield s
    for c in s.children:
        yield from _walk(c)


def _stages(call):
    """The call's stage children (a first prove's `compile/*` children of a
    dispatch are not stages)."""
    return [c for c in call.children if c.name.startswith(call.name + "/")]


def _check_marks(tr) -> tuple:
    """The device timeline (ISSUE 40): one mark a dispatch span, of that
    span's call; an upload mark only inside an `encode` of its call;
    besides them only the quotient's folds and its inverse, which have no
    dispatch span. Returns (folds, inverses)."""
    def calls(stage):
        return collections.Counter(
            s.name.rsplit("/", 1)[0] for s in _walk(tr.root)
            if s.name.endswith("/" + stage))

    kinds = [kind for kind, *_ in tr.device]
    up = "/" + tracing.UPLOAD
    uploads = collections.Counter(k[:-len(up)] for k in kinds
                                  if k.endswith(up))
    marked = collections.Counter(k for k in kinds if not k.endswith(up))
    encoded = calls(tracing.ENCODE)
    assert all(uploads[c] <= encoded[c] for c in uploads), (uploads, encoded)
    folds = marked.pop("quotient/expressions", 0)
    inverses = marked.pop("quotient/inverse", 0)
    assert marked == calls(tracing.DISPATCH)
    return folds, inverses


def _check_in_flight(call, stages):
    """Every wait is preceded by a dispatch since the last wait, and each
    stretch from that dispatch to the wait's end lies inside the call."""
    opened = None
    for s in stages:
        last = s.name.rsplit("/", 1)[-1]
        if last == tracing.DISPATCH and opened is None:
            opened = s
        elif last == tracing.WAIT:
            assert opened is not None, f"{s.name}: a wait with no dispatch"
            assert call.t0 <= opened.t0 <= s.t1 <= call.t1
            opened = None


class TestDeviceBoundarySpans:
    """Over conftest's `tiny_tpu_prove`: one tiny prove on TpuBackend
    (XLA:CPU here) under a trace, on a 1x1 mesh as the one-chip cell runs
    it."""

    def test_proof_bytes_equal_cpu_backend(self, tiny_tpu_prove,
                                           tiny_cpu_proof):
        assert tiny_tpu_prove.proof == tiny_cpu_proof

    @pytest.mark.parametrize("op,mesh,want", [
        ("msm", None, MSM_STAGES), ("msm", "1x1", CHUNK_MSM_STAGES),
        ("ntt", None, TWO_CROSSINGS), ("intt_many", None, TWO_CROSSINGS),
        ("msm_many", None, MESH_MSM_STAGES),
        ("msm_many", "1x1", CHUNK_MSM_STAGES)])
    def test_backend_call_has_its_stages_in_order(self, tiny_tpu_prove, op,
                                                  mesh, want, monkeypatch):
        import numpy as np

        from spectre_tpu.parallel.plan import current_plan
        from spectre_tpu.plonk import backend as B

        t = tiny_tpu_prove
        n = t.pk.vk.config.n
        arr = B.to_arr(range(1, n + 1))
        omega = t.pk.vk.domain.omega
        cpu_point = B.get_backend("cpu").msm(t.srs.g1_powers, arr)
        if mesh:
            monkeypatch.setenv("SPECTRE_MESH_SHAPE", mesh)
        one_chip = current_plan().n_devices == 1
        if op == "msm_many" and one_chip and not mesh:
            pytest.skip("the mesh branch of msm_many needs a mesh")
        with tracing.trace(f"one-{op}") as tr:
            if op == "msm":
                assert t.bk.msm(t.srs.g1_powers, arr) == cpu_point
            elif op == "msm_many":
                assert t.bk.msm_many(t.srs.g1_powers, [arr, arr]) \
                    == [cpu_point, cpu_point]
            elif op == "ntt":
                got = t.bk.ntt(arr, omega)
                assert np.array_equal(got, B.get_backend("cpu").ntt(arr, omega))
            else:
                got = t.bk.intt_many([arr, arr, arr], omega)
                assert len(got) == 3 and np.array_equal(
                    got[2], B.get_backend("cpu").intt(arr, omega))
        (call,) = tr.root.children
        assert call.name == f"backend/{op}"
        assert call.meta["n"] == n
        assert _check_marks(tr) == (0, 0)
        stages = _stages(call)
        assert [s.name for s in stages] == [f"backend/{op}/{w}" for w in want]
        _check_in_flight(call, stages)
        # what is shipped and what comes back, from the shapes: 64 bytes a
        # field element as 16 u32 limbs, 32 as the packed rows an NTT
        # kind's input goes up as (its Montgomery limbs go up once more,
        # for from_mont); a batch of 3 is padded to 4; a run of the
        # one-chip MSM path ships its columns and reads the affine points
        # (x, y, z) of its whole width
        rows = n * (4 if op == "intt_many" else 1)
        got = tracing.summary(tr)
        moved = got["transfer_bytes"]
        if one_chip and op in ("msm", "msm_many"):
            batch = 1 if op == "msm" else 2
            assert (call.meta["batch"], call.meta["width"]) \
                == (batch, MSM_WIDTH)
            # the window its columns were committed with, on the span and
            # in the manifest's sums
            assert call.meta["c"] == MSM_WINDOWS["vanilla"]
            assert got["msm_window"] == [call.meta["c"]]
            # the windows its columns ran: 1..n are 7 bits wide, two
            # windows of 4 a column; and what the base was said to be
            assert call.meta["active"] == 2 * batch \
                == got["msm_window_passes"]
            assert call.meta["basis"] == "powers"
            assert moved == {"h2d": 64 * n * batch,
                             "d2h": MSM_WIDTH * 3 * 64}
            assert got["msm_columns"] == {"real": batch,
                                          "padded": MSM_WIDTH - batch}
        elif op == "msm":
            assert moved == {"h2d": 64 * n, "d2h": 3 * 64}
            assert got["msm_columns"] == {"real": 0, "padded": 0}
            assert got["msm_window"] == [] and got["msm_window_passes"] == 0
            assert call.meta["basis"] == "powers"
        elif op == "msm_many":
            assert call.meta["batch"] == 2 and moved["d2h"] == 2 * 6 * 64
        else:
            assert moved == {"h2d": (32 + 64) * rows, "d2h": 2 * 64 * rows}

    def test_every_call_of_a_prove_has_its_stages(self, tiny_tpu_prove):
        calls = [s for s in _walk(tiny_tpu_prove.trace.root)
                 if s.name.startswith("backend/") and s.name.count("/") == 1]
        assert {c.name for c in calls} == {
            "backend/msm", "backend/msm_many", "backend/ntt", "backend/intt",
            "backend/intt_many"}
        for call in calls:
            want = CHUNK_MSM_STAGES if "msm" in call.name else TWO_CROSSINGS
            stages = _stages(call)
            assert [s.name.rsplit("/", 1)[-1] for s in stages] == want
            _check_in_flight(call, stages)

    def test_every_dispatch_of_a_prove_marks_the_device_timeline(
            self, tiny_tpu_prove):
        tr = tiny_tpu_prove.trace
        folds, inverses = _check_marks(tr)
        assert folds == 1 and inverses == 1
        dev = tracing.summary(tr)["device"]
        assert dev["marks"] == len(tr.device) and dev["interleaved"] == 0
        assert set(dev["by_kind"]) == {"msm", "ntt", "quotient", "upload"}
        assert 0 < dev["busy_lower_s"] <= dev["busy_s"]
        assert dev["busy_s"] + dev["idle_s"] == pytest.approx(
            tr.root.t1 - tr.root.t0, abs=1e-5)
        # stretches in stamp order, one device: none overlaps the next
        for a, b in zip(tr.device, tr.device[1:]):
            assert a[5] <= b[3] <= b[4] <= b[5]

    def test_quotient_is_one_queue_with_one_read(self, tiny_tpu_prove):
        (q,) = [s for s in _walk(tiny_tpu_prove.trace.root)
                if s.name == "prove/quotient"]
        # (a listener another test installed may add `compile/*` children)
        parts = [c for c in q.children if c.name.startswith("quotient/")]
        assert [c.name for c in parts] == [
            "quotient/extend", "quotient/expressions", "quotient/wait",
            "quotient/decode"]
        extend = _stages(parts[0])
        assert [s.name for s in extend] == [
            "quotient/extend/encode", "quotient/extend/dispatch"] \
            * (len(extend) // 2)
        # in flight from the first LDE dispatch to the end of the one read
        waits = [s for s in _walk(q) if s.name.endswith("/wait")]
        assert [s.name for s in waits] == ["quotient/wait"]
        assert q.t0 <= extend[1].t0 <= waits[0].t1 <= q.t1
        assert not [s for s in _walk(parts[1])
                    if s.name.endswith(("/wait", "/decode"))]

    def test_only_the_phases_are_named_prove(self, tiny_tpu_prove):
        import glob
        import os

        import spectre_tpu

        names = {s.name for s in _walk(tiny_tpu_prove.trace.root)}
        assert {n for n in names if n.startswith("prove/")} \
            <= PROVE_PHASES
        assert {"job/blind", "commit/marshal", "grand_products/perm_chunk",
                "grand_products/lookup", "evals/horner", "multiopen/h_poly",
                "multiopen/h_poly/combine", "multiopen/h_poly/remainder",
                "multiopen/linearisation",
                "multiopen/w2_division"} <= names
        # and in the source: `prove/...` is opened by phase(), never span()
        root = os.path.dirname(spectre_tpu.__file__)
        for path in glob.glob(os.path.join(root, "**", "*.py"),
                              recursive=True):
            with open(path) as f:
                src = f.read()
            assert not re.search(r'\bspan\(\s*f?"prove/', src), path
            for name in re.findall(r'phase\(\s*f?"(prove/[^"]*)"', src):
                assert name in PROVE_PHASES, (path, name)

    def test_span_counts_and_transfer_bytes_from_the_shapes(
            self, tiny_tpu_prove):
        from spectre_tpu.observability import manifest
        from spectre_tpu.plonk.constraint_system import NUM_H_CHUNKS
        from spectre_tpu.plonk.quotient_device import _ext_chunk

        t = tiny_tpu_prove
        cfg = t.pk.vk.config
        got = tracing.summary(t.trace)
        counts, moved = got["span_counts"], got["transfer_bytes"]
        man = manifest.build(job_id="j", method="m", trace=t.trace)
        assert man["span_counts"] == counts
        assert man["transfer_bytes"] == moved
        assert set(man["phase_seconds"]) == set(counts)
        # one MSM a committed column: advice and lookup advice, two permuted
        # columns a lookup, a grand product a permutation chunk and a
        # lookup, the quotient's chunks, W1 and W2; all but W1 and W2 reach
        # the backend in lists, each list one run of MSM_WIDTH columns here
        commits = (cfg.num_advice + cfg.num_lookup_advice
                   + 2 * cfg.num_lookup_advice
                   + cfg.num_perm_chunks + cfg.num_lookup_advice
                   + NUM_H_CHUNKS + 2)
        assert counts["backend/msm"] == 2
        runs = counts["backend/msm"] + counts["backend/msm_many"]
        assert got["msm_columns"] == man["msm_columns"] == {
            "real": commits, "padded": MSM_WIDTH * runs - commits}
        assert got["msm_window"] == man["msm_window"] \
            == [MSM_WINDOWS["vanilla"]]
        real = 0
        runs_seen = []
        for s in _walk(t.trace.root):
            if s.name in ("backend/msm", "backend/msm_many"):
                assert s.meta["width"] == MSM_WIDTH
                assert 1 <= s.meta["batch"] <= MSM_WIDTH
                real += s.meta["batch"]
                runs_seen.append((s.meta["basis"], s.meta["batch"],
                                  s.meta["active"]))
        assert real == commits
        # ISSUE 38: the witness's columns are committed as VALUES against
        # the Lagrange base with the windows their cells reach (the tiny
        # circuit's advice holds 28 at most, two windows of 4; its lookup
        # advice, the permuted column and the table stay under 16, one),
        # everything after them as coefficients with all of them; the
        # blinding rows never reach the device
        full = -(-254 // MSM_WINDOWS["vanilla"])
        assert runs_seen == [
            ("lagrange", 2, 2 + 1), ("lagrange", 2, 1 + 1),
            ("powers", cfg.num_perm_chunks + cfg.num_lookup_advice,
             full * (cfg.num_perm_chunks + cfg.num_lookup_advice)),
            ("powers", NUM_H_CHUNKS, full * NUM_H_CHUNKS),
            ("powers", 1, full), ("powers", 1, full)]
        assert got["msm_window_passes"] == man["msm_window_passes"] \
            == sum(r[2] for r in runs_seen) < full * commits
        for op in ("msm", "msm_many"):
            c = counts[f"backend/{op}"]
            assert [counts[f"backend/{op}/{w}"] for w in CHUNK_MSM_STAGES] \
                == [c] * 4
        for op in ("ntt", "intt", "intt_many"):
            c = counts[f"backend/{op}"]
            assert counts[f"backend/{op}/encode"] == 2 * c
            assert counts[f"backend/{op}/wait"] == 2 * c
            assert counts[f"backend/{op}/decode"] == c
        assert counts["job/blind"] == counts["quotient/wait"] == 1
        assert counts["grand_products/perm_chunk"] == cfg.num_perm_chunks
        assert counts["grand_products/lookup"] == cfg.num_lookup_advice
        # bytes: 64 a field element as limbs (the MSM scalars up, and
        # everything down), 32 as the packed rows a column goes up as since
        # ISSUE 39 (an NTT kind's first crossing, the quotient's stacks);
        # an NTT kind ships its rows up twice (packed, then its Montgomery
        # limbs back for from_mont) and down twice; a batch is padded to a
        # power of two; the quotient ships 3 synthetic rows, then whole
        # chunks, n rows a column (the device pads them to the extended
        # domain), and reads the extended domain once
        n, m = cfg.n, t.pk.vk.domain.n_ext
        rows = n * (counts["backend/ntt"] + counts["backend/intt"])
        for s in _walk(t.trace.root):
            if s.name == "backend/intt_many":
                rows += n * (1 << (s.meta["batch"] - 1).bit_length())
        lde_rows = 3 + _ext_chunk(m) * (counts["quotient/extend/encode"] - 1)
        assert moved == {
            "h2d": 64 * n * commits + (32 + 64) * rows + 32 * n * lde_rows,
            "d2h": 64 * (3 * MSM_WIDTH * runs + 2 * rows + m)}

    def test_span_meta_is_allocated_on_first_use(self):
        with tracing.trace("t-meta") as tr:
            with tracing.span("bare"):
                pass
            with tracing.span("shipped", bytes=8):
                tracing.annotate(n=2)
        bare, shipped = tr.root.children
        assert bare.meta is None and tr.root.meta is None
        assert shipped.meta == {"bytes": 8, "n": 2}
        ev = {e["name"]: e for e in tracing.chrome_trace(tr)["traceEvents"]}
        assert "args" not in ev["bare"]
        assert ev["shipped"]["args"] == {"bytes": 8, "n": 2}

    def test_profiler_session_holds_the_program_s_annotations(
            self, tiny_tpu_prove, tmp_path):
        """The shared clock: a jax.profiler session round one ntt, outside
        every job trace, holds the call and its stages by name."""
        import glob

        import jax
        from jax.profiler import ProfileData

        from spectre_tpu.plonk import backend as B

        t = tiny_tpu_prove
        arr = B.to_arr(range(t.pk.vk.config.n))
        assert tracing.active() is None
        jax.profiler.start_trace(str(tmp_path))
        try:
            t.bk.ntt(arr, t.pk.vk.domain.omega)
        finally:
            jax.profiler.stop_trace()
        (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        seen: dict = {}
        for plane in ProfileData.from_file(pb).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("backend/"):
                        seen[ev.name] = seen.get(ev.name, 0) + 1
        assert seen == {"backend/ntt": 1, "backend/ntt/encode": 2,
                        "backend/ntt/dispatch": 2, "backend/ntt/wait": 2,
                        "backend/ntt/decode": 1}

    def test_no_jitted_program_of_the_served_path_is_a_lambda(
            self, tiny_tpu_prove):
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk import quotient_device as QD

        B._mont_fns()
        names = {k: fn.__name__ for k, fn in B._mont_jits.items()}
        assert names == {"to": "to_mont_fr", "from": "from_mont_fr",
                         "toq": "to_mont_fq"}
        helpers = {k: fn.__name__ for k, fn in QD._helpers().items()}
        assert len(helpers) == 8
        assert all(n.startswith("quotient_") for n in helpers.values())
        assert len(set(helpers.values())) == 8     # one name a program


class TestBackendByteEquality:
    """The SAME proof bytes out of `CpuBackend` and `TpuBackend` when the
    blinding is seeded alike: the backends differ in WHERE the math runs,
    never in WHAT they compute. The one-chip plan's side of it is
    `TestDeviceBoundarySpans::test_proof_bytes_equal_cpu_backend`."""

    def test_cpu_tpu_proof_bytes_identical(self, tiny, tiny_cpu_proof):
        """Keygen and prove through `TpuBackend` on the default plan (all 8
        virtual devices: lists of commits take the mesh's data-parallel
        branch), against the CpuBackend key and seeded proof."""
        pk, bk = _tpu_keygen(tiny)
        proof = _seeded_prove(tiny, pk, bk)
        assert pk.vk.digest() == tiny.pk.vk.digest()
        assert verify(pk.vk, tiny.srs, tiny.instances, proof)
        assert proof == tiny_cpu_proof, \
            "backend proof bytes diverge (transcript/serialization drift)"


class TestNttModeProofBytes:
    """The ISSUE-4 correctness gate, mirroring TestMsmModeCommitments:
    radix2 and fourstep must yield BYTE-IDENTICAL proofs through the device
    backend under seeded blinding — the modes change kernel work shape,
    never a single transformed value. The radix2 side is this file's shared
    tiny key and prove (`tiny_tpu_prove`, one-chip plan); the fourstep
    keygen and prove are the only new work here."""

    def test_proof_bytes_identical_across_ntt_modes(self, tiny, tiny_tpu_prove,
                                                    monkeypatch):
        from spectre_tpu.ops import ntt as NTT

        assert NTT.ntt_mode() == "radix2"     # what the shared prove ran
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "1x1")
        monkeypatch.setenv("SPECTRE_NTT_MODE", "fourstep")
        pk, bk = _tpu_keygen(tiny)
        proof = _seeded_prove(tiny, pk, bk)
        assert pk.vk.digest() == tiny_tpu_prove.pk.vk.digest()
        assert verify(pk.vk, tiny.srs, tiny.instances, proof)
        assert verify(pk.vk, tiny.srs, tiny.instances, tiny_tpu_prove.proof)
        assert proof == tiny_tpu_prove.proof, \
            "SPECTRE_NTT_MODE changed proof bytes (modes must be identical)"


class TestOneChipBatchedCommit:
    """ISSUE 30: on ONE device, in the default MSM mode, `TpuBackend`
    commits a list MSM.CHUNK_WIDTH columns a device run (`_msm_chunks`: a
    window phase a column, one combine, one affine conversion and one read
    a run) and a single column as a chunk of one. Same group elements as
    the one-column kernels and as the native Pippenger; bytes and counts
    only."""

    N = MSM_N

    @pytest.fixture(scope="class")
    def base(self):
        pts = [bn.g1_curve.mul(bn.G1_GEN, 3 * k + 2) for k in range(self.N)]
        pts[5] = None                      # an infinity in the base
        return host.points_to_limbs(pts)

    @pytest.fixture(scope="class")
    def columns(self):
        import random
        rng = random.Random(30)
        cols = [B.to_arr([rng.randrange(bn.R) for _ in range(self.N)])
                for _ in range(17)]
        cols[1] = B.zeros(self.N)          # an all-zero column
        cols[2] = cols[2][:self.N - 5]     # shorter than the base
        return cols

    @pytest.fixture()
    def one_chip(self, monkeypatch):
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "1x1")
        monkeypatch.delenv("SPECTRE_MSM_MODE", raising=False)
        return B.TpuBackend()

    @pytest.mark.parametrize("length", [1, 2, 3, 10, 16, 17])
    def test_chunks_equal_the_loop_and_the_cpu(self, base, columns,
                                               one_chip, length):
        import jax.numpy as jnp

        from spectre_tpu.ops import ec, limbs as L16, msm as MSM

        cols = columns[:length]
        with tracing.trace(f"chunks-{length}") as tr:
            got = one_chip.msm_many(base, cols)
        assert got == B.get_backend("cpu").msm_many(base, cols)
        # the loop the batched path replaced: one column a program (the
        # short column zero-extended, so that it is the same program)
        pts = one_chip._base_points(base, self.N)
        for col, pt in list(zip(cols, got))[:3]:
            full = np.zeros((self.N, 4), dtype=np.uint64)
            full[:col.shape[0]] = col
            sc16 = jnp.asarray(L16.u64limbs_to_u16limbs(full))
            assert ec.decode_points(MSM.msm(pts, sc16)[None])[0] == pt
        if length > 1:
            assert got[1] is None                   # the all-zero column
        # a list longer than the width is split, a shorter one padded
        runs = tr.root.children
        assert [(r.name, r.meta["batch"], r.meta["width"]) for r in runs] \
            == [("backend/msm_many", min(16, length - at), 16)
                for at in range(0, length, 16)]
        assert tracing.summary(tr)["msm_columns"] == {
            "real": length, "padded": 16 * len(runs) - length}

    def test_msm_is_a_chunk_of_one(self, base, columns, one_chip):
        cpu = B.get_backend("cpu")
        with tracing.trace("chunk-of-one") as tr:
            # (not the short column: alone it is an MSM of its own length,
            # and a window-phase program of its own)
            for col in (columns[0], columns[1], columns[3]):
                assert one_chip.msm(base, col) == cpu.msm(base, col)
        assert [(r.name, r.meta["batch"], r.meta["width"])
                for r in tr.root.children] == [("backend/msm", 1, 16)] * 3

    def test_another_mode_loops_msm(self, base, columns, one_chip,
                                    monkeypatch):
        """Default mode on one device only: another MSM mode loops `msm`, a
        column a call, on that mode's own kernels (held to the CPU's bytes
        through this backend by tests/test_msm_modes.py::
        TestMsmModeCommitments, where their programs are compiled)."""
        monkeypatch.setenv("SPECTRE_MSM_MODE", "glv")
        calls = []
        monkeypatch.setattr(
            one_chip, "msm",
            lambda points, sc, base_key=None, basis="powers":
            calls.append(sc) or len(calls))
        with tracing.trace("not-batched") as tr:
            assert one_chip.msm_many(base, columns[:2]) == [1, 2]
        assert [c is col for c, col in zip(calls, columns)] == [True, True]
        assert tracing.summary(tr)["msm_columns"] == {"real": 0, "padded": 0}

    def test_a_mesh_keeps_the_data_parallel_branch(self, base, columns,
                                                   one_chip, monkeypatch):
        """All 8 virtual devices once the 1x1 shape is unset."""
        monkeypatch.delenv("SPECTRE_MESH_SHAPE")
        with tracing.trace("not-batched") as tr:
            got = one_chip.msm_many(base, columns[:2])
        assert got == B.get_backend("cpu").msm_many(base, columns[:2])
        assert tracing.summary(tr)["msm_columns"] == {"real": 0, "padded": 0}
