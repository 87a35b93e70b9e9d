"""Static-analysis subsystem (spectre_tpu.analysis): finding/baseline
mechanics, circuit-audit rules, kernel-lint rules, trace-cache hygiene
rules — including the seeded MUTATION checks: a deliberately
under-constrained cell, an over-degree expression, a limb-overflow
multiply, a fresh-per-call jit, and a row-level coverage hole must each
be flagged (the auditor's reason to exist is that nothing else notices
these), while the clean live tree produces ZERO findings."""

import dataclasses
import json
import random
import time

import numpy as np
import pytest

from spectre_tpu.analysis import (Finding, Severity, audit_context,
                                  audit_rows, load_baseline,
                                  partition_findings, write_baseline)
from spectre_tpu.analysis.circuit_audit import expression_degrees
from spectre_tpu.analysis.kernel_lint import (KERNELS, lint_fn, lint_kernel,
                                              lint_limbs_host)
from spectre_tpu.builder.context import Context
from spectre_tpu.builder.range_chip import RangeChip
from spectre_tpu.plonk.constraint_system import CircuitConfig
from spectre_tpu.plonk.expressions import all_expressions


def _small_circuit():
    """A clean little range-checked multiply circuit."""
    random.seed(0)
    ctx = Context()
    rng = RangeChip(lookup_bits=4)
    g = rng.gate
    a = ctx.load_witness(3)
    b = ctx.load_witness(5)
    c = g.mul(ctx, a, b)
    rng.range_check(ctx, a, 4)
    ctx.expose_public(c)
    cfg = ctx.auto_config(k=7, lookup_bits=4)
    return ctx, cfg


class TestFindings:
    def test_key_defaults_and_partition(self):
        f1 = Finding("circuit", "CA-X", Severity.ERROR, "f.py", "obj", "m")
        assert f1.key == "CA-X:obj"
        f2 = Finding("circuit", "CA-Y", Severity.WARNING, "f.py", "obj", "m",
                     key="CA-Y:obj:7")
        active, suppressed = partition_findings(
            [f1, f2], {"CA-Y:obj:7": "accepted"})
        assert active == [f1] and suppressed == [f2]

    def test_baseline_roundtrip(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        f = Finding("kernel", "KL-X", Severity.ERROR, "f.py", "k", "msg",
                    key="KL-X:k:1")
        write_baseline([f], path, reason="test")
        bl = load_baseline(path)
        assert "KL-X:k:1" in bl
        with open(path) as fh:
            assert json.load(fh)["suppressions"][0]["key"] == "KL-X:k:1"

    def test_severity_order(self):
        assert Severity.at_least("error", "warning")
        assert not Severity.at_least("warning", "error")


class TestCircuitAudit:
    def test_clean_circuit_has_no_findings(self):
        ctx, cfg = _small_circuit()
        assert audit_context(ctx, cfg, "clean") == []

    def test_flags_seeded_underconstrained_cell(self):
        """THE mutation check: a witness cell no constraint touches."""
        ctx, cfg = _small_circuit()
        ctx.load_witness(999)  # assigned, never referenced by anything
        cfg2 = ctx.auto_config(k=7, lookup_bits=4)
        rules = [f.rule for f in audit_context(ctx, cfg2, "seeded")]
        assert "CA-UNDERCONSTRAINED" in rules

    def test_flags_seeded_degree_violation(self):
        """Injected expression of column-degree 5 > budget 4."""
        ctx, cfg = _small_circuit()

        def evil(cfg_, c, beta, gamma):
            yield from all_expressions(cfg_, c, beta, gamma)
            v = c.var(("adv", 0), 0)
            yield c.mul(c.mul(c.mul(c.mul(v, v), v), v), v)

        fs = audit_context(ctx, cfg, "deg", expressions_fn=evil)
        assert any(f.rule == "CA-DEGREE" for f in fs)
        # the real expression set stays inside the budget
        assert all(d <= cfg.max_expr_degree for d in expression_degrees(cfg))

    def test_real_expression_degrees_within_budget(self):
        # incl. the wide-SHA region identities (selector x bit-cubics)
        cfg = CircuitConfig(k=10, num_advice=1, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=8, num_sha_slots=1)
        degs = expression_degrees(cfg)
        assert degs and max(degs) <= cfg.max_expr_degree

    def test_flags_copy_orphan(self):
        ctx, cfg = _small_circuit()
        ctx.copies.append((("adv", 10 ** 6), ("adv", 0)))
        fs = audit_context(ctx, cfg, "orphan")
        assert any(f.rule == "CA-COPY-ORPHAN" for f in fs)

    def test_flags_unbound_lookup_table(self):
        ctx, _ = _small_circuit()
        ctx.lkp_streams.setdefault("nibble_op", []).append(5)
        cfg = CircuitConfig(k=7, num_advice=2, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=4,
                            lookup_tables=("range",))
        fs = audit_context(ctx, cfg, "tbl")
        assert any(f.rule == "CA-TABLE-UNBOUND" for f in fs)

    def test_flags_dead_columns(self):
        ctx = Context()
        v = ctx.load_witness(5)
        ctx.expose_public(v)  # referenced, so not under-constrained
        cfg = CircuitConfig(k=7, num_advice=1, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=4)
        rules = [f.rule for f in audit_context(ctx, cfg, "dead")]
        assert "CA-DEAD-SELECTOR" in rules and "CA-DEAD-FIXED" in rules


class TestRowAudit:
    """Row-wise gate-coverage auditor (ISSUE 16 tentpole): coverage holes
    in the PHYSICAL assignment grid that the stream-level rules miss."""

    def test_clean_circuit_rows_clean(self):
        ctx, cfg = _small_circuit()
        assert audit_rows(ctx, cfg, "clean") == []

    def test_flags_seeded_row_unbound(self):
        """THE row-level mutation: a placed cell drifts to a row no gate
        window covers and no copy endpoint binds — a free witness row."""
        ctx, cfg = _small_circuit()

        def mutate(placement, selectors, copies):
            placement[max(placement)] = (0, cfg.usable_rows - 2)
            return placement, selectors, copies

        fs = audit_rows(ctx, cfg, "rowmut", mutate=mutate)
        assert any(f.rule == "CA-ROW-UNBOUND"
                   and f.severity == Severity.ERROR for f in fs)

    def test_flags_seeded_dead_selector_row(self):
        """A selector armed over rows its gate window never reads from."""
        ctx, cfg = _small_circuit()

        def mutate(placement, selectors, copies):
            selectors[0][cfg.usable_rows - 8] = 1
            return placement, selectors, copies

        fs = audit_rows(ctx, cfg, "deadsel", mutate=mutate)
        assert any(f.rule == "CA-ROW-DEAD-SELECTOR" for f in fs)

    def test_flags_stale_sha_slot_selectors(self):
        """Config allocates a SHA slot the circuit never fills: the
        structural selectors gate all-zero rows — vacuous activation."""
        ctx, cfg = _small_circuit()
        cfg2 = dataclasses.replace(cfg, num_sha_slots=1)
        fs = audit_rows(ctx, cfg2, "shastale")
        assert any(f.rule == "CA-ROW-DEAD-SELECTOR" and ":sha" in f.key
                   for f in fs)

    def test_row_mutate_does_not_poison_caches(self):
        """The mutate hook gets copies: a seeded mutant must not leak
        into the Context's layout/placement caches."""
        ctx, cfg = _small_circuit()

        def mutate(placement, selectors, copies):
            placement[max(placement)] = (0, cfg.usable_rows - 2)
            selectors[0][0] = 0
            return placement, selectors, copies

        assert audit_rows(ctx, cfg, "m", mutate=mutate) != []
        assert audit_rows(ctx, cfg, "clean-again") == []

    def test_audit_context_threads_row_mutate(self):
        ctx, cfg = _small_circuit()

        def mutate(placement, selectors, copies):
            placement[max(placement)] = (0, cfg.usable_rows - 2)
            return placement, selectors, copies

        rules = [f.rule for f in audit_context(ctx, cfg, "threaded",
                                               row_mutate=mutate)]
        assert "CA-ROW-UNBOUND" in rules


class TestKernelLint:
    def test_flags_seeded_limb_overflow_multiply(self):
        """THE mutation check: 17-bit limbs leave no headroom in u32."""
        import jax.numpy as jnp
        a = jnp.zeros((4, 16), jnp.uint32)
        fs = lint_fn(lambda x, y: x * y, (a, a), name="mut.widemul",
                     file="x.py", in_bits=17)
        assert [f.rule for f in fs] == ["KL-OVERFLOW"]
        # 16-bit limbs fit exactly: (2^16-1)^2 < 2^32
        assert lint_fn(lambda x, y: x * y, (a, a), name="mut.mul16",
                       file="x.py", in_bits=16) == []

    def test_mask_consumed_product_is_exempt(self):
        import jax.numpy as jnp
        a = jnp.zeros((4, 16), jnp.uint32)
        fs = lint_fn(lambda x, y: (x * y) & np.uint32(0xFFFF), (a, a),
                     name="mut.masked", file="x.py", in_bits=17)
        assert fs == []

    def test_flags_unreduced_add_chain(self):
        import jax.numpy as jnp
        a = jnp.zeros((4, 16), jnp.uint32)

        def chain(x):
            acc = x
            for _ in range(17):  # 2^17 summands of 2^16-1 overflow u32
                acc = acc + acc
            return acc

        fs = lint_fn(chain, (a,), name="mut.chain", file="x.py", in_bits=16)
        assert any(f.rule == "KL-OVERFLOW" for f in fs)

    def test_flags_float_in_field_kernel(self):
        import jax.numpy as jnp
        a = jnp.zeros((4, 16), jnp.uint32)
        fs = lint_fn(lambda x: (x.astype(jnp.float32) * 2.0)
                     .astype(jnp.uint32),
                     (a,), name="mut.float", file="x.py")
        assert any(f.rule == "KL-FLOAT" for f in fs)

    def test_flags_host_callback(self):
        import jax
        import jax.numpy as jnp
        a = jnp.zeros((4, 16), jnp.uint32)

        def cb(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        fs = lint_fn(cb, (a,), name="mut.cb", file="x.py")
        assert any(f.rule == "KL-CALLBACK" for f in fs)

    def test_real_field_kernels_clean(self):
        for spec in KERNELS:
            if spec.name in ("field_ops.mont_mul", "field_ops.add",
                             "ntt.ntt", "sha256.compress"):
                assert lint_kernel(spec) == [], spec.name

    def test_limbs_host_probe_clean(self):
        assert lint_limbs_host() == []


# --------------------------------------------------------------------------
# trace-cache hygiene lint (ISSUE 16 tentpole)
# --------------------------------------------------------------------------

# regression fixture: the pre-ISSUE-13 sharded_msm shape — a fresh
# shard_map closure wrapped in a fresh jit on EVERY call (the MULTICHIP
# rc=124 root cause)
_FRESH_SHARD_SRC = '''\
import functools

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def sharded_msm(points, scalars, c, mesh):
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data")), out_specs=P())
    def run(p, s):
        return (p * s).sum()

    return jax.jit(run)(points, scalars)
'''

_EXEMPT_SRC = '''\
import functools

import jax

_RUNNERS = {}

TRACE_RUNNER_CACHES = (("_get_runner", "_RUNNERS"),)


def _get_runner(c):
    fn = _RUNNERS.get(c)
    if fn is None:
        fn = jax.jit(lambda x: x * c)
        _RUNNERS[c] = fn
    return fn


@functools.cache
def _memo_runner(c):
    return jax.jit(lambda x: x + c)


@jax.jit
def entry(x):
    return jax.jit(lambda v: v)(x)
'''

_CONSTCAP_SRC = '''\
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LUT = jnp.arange(8)


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + _LUT


@jax.jit
def call(x):
    return pl.pallas_call(_kernel, out_shape=x)(x)
'''

_UNSTABLE_SRC = '''\
import functools

import jax


@functools.partial(jax.jit, static_argnums=(1,), static_argnames=("mode",))
def kernel(x, shape, mode="std"):
    return x


def caller(x):
    return kernel(x, [4, 4], mode="std")


def caller2(x):
    return kernel(x, (4, 4), mode={"a": 1})
'''

_UNDECLARED_SRC = '''\
import jax

_RUNNERS = {}


def _build(key):
    fn = jax.jit(lambda x: x)
    _RUNNERS[key] = fn
    return fn
'''

_STALE_SRC = '''\
import jax

_RUNNERS = {}

TRACE_RUNNER_CACHES = (("_vanished", "_RUNNERS"),)
TRACE_JIT_ROOTS = ("also_gone",)
'''


def _scan_src(tmp_path, src, name="fixture_mod.py"):
    from spectre_tpu.analysis.trace_lint import scan_files
    p = tmp_path / name
    p.write_text(src)
    return scan_files([str(p)])


class TestTraceLintStatic:
    def test_live_tree_static_scan_clean(self):
        """The whole ops/ + parallel/ + plonk/ tree honors the trace-cache
        discipline: zero findings, no baseline entries needed."""
        from spectre_tpu.analysis.trace_lint import scan_files
        assert scan_files() == []

    def test_fresh_jit_regression_fixture(self, tmp_path):
        """ISSUE 16 satellite: the PR 13 fresh-closure shard_map pattern,
        re-created in a throwaway module, trips TC-FRESH-JIT and NOTHING
        else."""
        fs = _scan_src(tmp_path, _FRESH_SHARD_SRC)
        assert fs and {f.rule for f in fs} == {"TC-FRESH-JIT"}
        assert {f.severity for f in fs} == {Severity.ERROR}
        assert all("sharded_msm" in f.key for f in fs)
        # both constructions inside the body are flagged: the shard_map
        # decorator closure AND the per-call jit wrap
        assert {k for _, _, k in
                (f.key.rsplit(":", 2) for f in fs)} == {"jit", "shard_map"}

    def test_fresh_jit_exemptions(self, tmp_path):
        """Runner-cache stores, functools.cache builders, and jit-inside-
        jit (outer jit caches the trace) are NOT fresh-jit findings."""
        assert _scan_src(tmp_path, _EXEMPT_SRC) == []

    def test_flags_pallas_const_capture(self, tmp_path):
        """THE mutation check for the PR 15 class: a kernel body reading a
        module-level concrete-array binding."""
        fs = _scan_src(tmp_path, _CONSTCAP_SRC)
        assert {f.rule for f in fs} == {"TC-CONST-CAPTURE"}
        assert "_LUT" in fs[0].key

    def test_flags_unstable_static_args(self, tmp_path):
        fs = _scan_src(tmp_path, _UNSTABLE_SRC)
        assert {f.rule for f in fs} == {"TC-UNSTABLE-STATIC"}
        assert len(fs) == 2  # list at static position, dict static kwarg

    def test_flags_undeclared_runner_cache(self, tmp_path):
        fs = _scan_src(tmp_path, _UNDECLARED_SRC)
        assert {f.rule for f in fs} == {"TC-UNCACHED-RUNNER"}
        assert fs[0].key.endswith("_build:_RUNNERS")

    def test_flags_stale_registry_entries(self, tmp_path):
        fs = _scan_src(tmp_path, _STALE_SRC)
        assert {f.rule for f in fs} == {"TC-UNCACHED-RUNNER"}
        keys = sorted(f.key for f in fs)
        assert any(k.endswith(":stale") for k in keys)
        assert any(k.endswith(":root") for k in keys)

    def test_registry_ast_matches_live_imports(self):
        """The AST view of TRACE_RUNNER_CACHES (what the lint scans) and
        the live-import view (plan.runner_registry) agree module by
        module — declarative drift in either direction is a failure."""
        import ast
        import importlib

        from spectre_tpu.analysis.trace_lint import _module_toplevel
        from spectre_tpu.parallel.plan import runner_registry
        live = runner_registry()
        assert live  # contract has participants
        for modname, declared in live.items():
            path = importlib.import_module(modname).__file__
            with open(path) as fh:
                tree = ast.parse(fh.read())
            _n, _a, ast_pairs, _r = _module_toplevel(tree)
            assert set(declared) == ast_pairs, modname
            assert declared, f"{modname} declares no runner caches"


class TestTraceLintDynamic:
    def test_retrace_probe_flags_fresh_jit(self):
        """THE dynamic mutation check: a runner that mints a fresh jit per
        call compiles on the second call -> TC-RETRACE-DYN."""
        import jax
        import jax.numpy as jnp

        from spectre_tpu.analysis.trace_lint import ProbeSpec, run_probe

        def build():
            x = jnp.zeros((4,), jnp.uint32)

            def run(v):
                return jax.jit(lambda t: t + jnp.uint32(1))(v)

            return run, (x,)

        fs = run_probe(ProbeSpec("mutant.fresh", "x.py", build))
        assert [f.rule for f in fs] == ["TC-RETRACE-DYN"]
        assert fs[0].key == "TC-RETRACE-DYN:mutant.fresh"
        assert fs[0].severity == Severity.ERROR

    @pytest.mark.slow
    def test_probes_clean_and_within_budget(self):
        """ISSUE 16 satellite: the full probe suite (every registered
        runner family, double-called at tiny shapes) is clean on the live
        tree AND fits the 120s lint-deep budget on a 1-core CPU host.

        slow-marked: ~90s of probe compiles on the 1-core box — runs in
        `make test-slow` (no marker filter; `make test`'s lint-deep drives
        the same probes), stays out of the 870s tier-1 window."""
        from spectre_tpu.analysis.trace_lint import PROBES, run_probes
        assert len(PROBES) == 7
        t0 = time.monotonic()
        fs = run_probes()
        dt = time.monotonic() - t0
        assert fs == [], [f.key for f in fs]
        assert dt < 120, f"probe suite took {dt:.1f}s (budget 120s)"


class TestCLI:
    def test_kernel_engine_exit_clean(self, tmp_path, capsys):
        from spectre_tpu.analysis.__main__ import main
        out = str(tmp_path / "findings.json")
        rc = main(["--engine", "kernel", "--kernels",
                   "field_ops.add,limbs.host", "--json", out, "-q"])
        assert rc == 0
        data = json.load(open(out))
        assert data["active"] == []

    def test_trace_engine_json_payload(self, tmp_path):
        """ISSUE 16 satellite: --json is machine-readable — findings plus
        per-pass runtimes plus per-engine root counts."""
        from spectre_tpu.analysis.__main__ import main
        out = str(tmp_path / "trace.json")
        rc = main(["--engine", "trace", "--no-probes", "--json", out, "-q"])
        assert rc == 0
        data = json.load(open(out))
        assert data["active"] == [] and data["suppressed"] == []
        names = [p["name"] for p in data["passes"]]
        assert names == ["trace static scan"]
        p = data["passes"][0]
        assert p["engine"] == "trace" and p["findings"] == 0
        assert isinstance(p["seconds"], float)
        assert data["roots"]["trace_files"] > 10
        assert data["roots"]["trace_probes"] == 0  # --no-probes
        assert data["seconds"] >= p["seconds"]

    def test_trace_engine_fail_on_gates_exit(self, tmp_path, monkeypatch):
        """A seeded trace finding flips the trace-engine exit code."""
        from spectre_tpu.analysis import __main__ as M
        from spectre_tpu.analysis import trace_lint as TL

        def fake_scan(paths=None):
            return [Finding("trace", "TC-FRESH-JIT", Severity.ERROR,
                            "x.py", "m:f", "seeded",
                            key="TC-FRESH-JIT:x.py:f:jit")]

        monkeypatch.setattr(TL, "scan_files", fake_scan)
        monkeypatch.setattr(TL, "PROBES", [])
        empty = str(tmp_path / "empty.json")
        assert M.main(["--engine", "trace", "--baseline", empty, "-q"]) == 1
        bl = str(tmp_path / "bl.json")
        assert M.main(["--engine", "trace", "--baseline", bl,
                       "--write-baseline", "-q"]) == 0
        assert M.main(["--engine", "trace", "--baseline", bl, "-q"]) == 0

    def test_fail_on_gates_exit_code(self, tmp_path, monkeypatch):
        """A seeded finding must flip the exit code unless baselined."""
        from spectre_tpu.analysis import __main__ as M
        from spectre_tpu.analysis import kernel_lint as KL
        import jax.numpy as jnp

        def fake_all(names=None):
            a = jnp.zeros((2, 16), jnp.uint32)
            return lint_fn(lambda x, y: x * y, (a, a), name="mut.cli",
                           file="x.py", in_bits=17)

        monkeypatch.setattr(KL, "lint_all_kernels", fake_all)
        empty = str(tmp_path / "empty.json")
        rc = M.main(["--engine", "kernel", "--baseline", empty, "-q"])
        assert rc == 1
        # accept into a baseline -> clean
        bl = str(tmp_path / "bl.json")
        assert M.main(["--engine", "kernel", "--baseline", bl,
                       "--write-baseline", "-q"]) == 0
        assert M.main(["--engine", "kernel", "--baseline", bl, "-q"]) == 0


class TestShippedBaseline:
    def test_repo_baseline_still_empty(self):
        """ISSUE 6 satellite: the shipped analysis baseline must stay EMPTY
        — a suppression sneaking in here would silently accept a real
        circuit-soundness or kernel-lint finding. Grow it only with an
        explicit, reviewed `--write-baseline` run."""
        import os

        import spectre_tpu.analysis as A
        path = os.path.join(os.path.dirname(A.__file__), "baseline.json")
        with open(path) as fh:
            data = json.load(fh)
        assert data == {"suppressions": []}

    def test_new_passes_need_no_baseline(self):
        """ISSUE 16 satellite: the trace scan and the row auditor landed
        against the EMPTY shipped baseline — the live tree is clean under
        both new passes without a single suppression."""
        from spectre_tpu.analysis.circuit_audit import audit_rows as AR
        from spectre_tpu.analysis.circuits import AUDIT_CIRCUITS
        from spectre_tpu.analysis.trace_lint import scan_files
        assert scan_files() == []
        ctx, cfg, name = AUDIT_CIRCUITS["committee_update"]()
        assert AR(ctx, cfg, name) == []

    def test_matmul_cap_proof_needs_no_baseline(self):
        """ISSUE 19: the closed-form exactness proof of the shipped
        `_MATMUL_MAX_LOGN` (two-level carry split + 2^272 REDC) holds
        against the EMPTY baseline — the cap is proven, not asserted."""
        from spectre_tpu.analysis.kernel_lint import lint_matmul_cap
        from spectre_tpu.ops.ntt import _MATMUL_MAX_LOGN
        assert _MATMUL_MAX_LOGN >= 12
        assert lint_matmul_cap() == []
