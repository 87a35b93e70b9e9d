"""Engine 3: trace-cache hygiene lint (TC-*) over ops/, parallel/, plonk/.

The prover only hits hardware speed when every hot MSM/NTT/quotient call
reuses a compiled program. Both trace-cache bug classes this repo has
already paid for were found by hand:

  * ISSUE 13 (MULTICHIP rc=124): a fresh `shard_map` closure wrapped in a
    fresh `jax.jit` per call re-traced and re-lowered the full 8-way SPMD
    program for every MSM/NTT of a prove — ~60 multi-minute retraces on a
    1-core host, so the mesh prove never finished.
  * ISSUE 15 (Pallas MSM): a kernel body capturing a concrete traced array
    constant, which the Pallas lowering rejects (and which would otherwise
    bake a fresh constant into every trace).

This engine catches both classes mechanically, plus the registry drift
that would let them creep back:

  TC-FRESH-JIT       error  `jax.jit` / `shard_map` / `pallas_call`
                            constructed inside a function body with no
                            caching discipline: the enclosing function is
                            not `functools.cache`-decorated, is not itself
                            jit-decorated (an outer jit caches the trace),
                            and never stores into a module-level cache
                            dict. Every call mints a fresh traced program.
  TC-CONST-CAPTURE   error  a Pallas kernel body reads a module/closure
                            binding whose value is a concrete array
                            constructor (`jnp.asarray(...)`, ...) — the
                            PR 15 class; build the constant in-trace from
                            scalar literals instead.
  TC-UNSTABLE-STATIC error  a call site passes a list/dict/set/lambda/
                            comprehension at a `static_argnums` /
                            `static_argnames` position of a jitted entry
                            point: unhashable statics raise, and unstable
                            ones defeat the trace cache.
  TC-UNCACHED-RUNNER error  runner-registry drift: a function that builds
                            a jitted program and stores it in a module
                            cache dict is missing from that module's
                            `TRACE_RUNNER_CACHES` declaration — or a
                            declared entry went stale (builder or cache
                            renamed/removed). Same for `TRACE_JIT_ROOTS`
                            (module-level jitted entry points).
  TC-RETRACE-DYN     error  dynamic cross-check against
                            observability/compilelog: each registered
                            runner is called twice at a tiny shape and the
                            second call must trigger ZERO
                            `backend_compile` events (a warm trace cache).

The static rules are pure-AST (no imports of the scanned modules — ops/
modules cannot import parallel/ at import time, and the lint must not
care). The registry contract is declarative for the same reason: modules
that cache jitted runners declare `TRACE_RUNNER_CACHES = ((builder,
cache_dict), ...)` and modules with module-level jitted entry points
declare `TRACE_JIT_ROOTS = (name, ...)`; this engine cross-checks the
declarations against what the AST actually contains, and the dynamic
probe table below exercises the declared runners.

CLI: `python -m spectre_tpu.analysis --engine trace` (= `make lint-deep`).
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass

from .findings import Finding, Severity

_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO = os.path.dirname(_PKG)

# directories under spectre_tpu/ the static rules scan
SCAN_DIRS = ("ops", "parallel", "plonk")

# last dotted component of a call that mints a traced program
_JIT_NAMES = {"jit", "shard_map", "pallas_call"}
# decorators that make a per-call jit construction safe (memoized builder)
_CACHE_DECOS = {"cache", "lru_cache", "cached_property"}
# concrete-array constructors whose module/closure bindings a Pallas
# kernel body must not capture
_ARRAY_FNS = {"asarray", "array", "zeros", "ones", "full", "arange",
              "empty", "eye", "linspace"}
_ARRAY_MODULES = {"jnp", "np", "numpy", "jax"}
# calls that build unhashable values (flagged at static positions)
_UNHASHABLE_CTORS = {"list", "dict", "set", "bytearray"}
_UNHASHABLE_NODES = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.SetComp,
                     ast.DictComp, ast.GeneratorExp, ast.Lambda)


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def _dotted(node) -> str | None:
    """`jax.jit` -> "jax.jit", `pl.pallas_call` -> "pl.pallas_call"."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def _jit_kind(call: ast.Call) -> str | None:
    """The traced-program constructor a Call mints, or None.

    Matches direct calls (`jax.jit(f)`, `shard_map(...)`) and the partial
    idiom (`functools.partial(jax.jit, ...)`, used as decorator factory)."""
    name = _dotted(call.func)
    if name is None:
        return None
    if _last(name) in _JIT_NAMES:
        return _last(name)
    if _last(name) == "partial" and call.args:
        inner = _dotted(call.args[0])
        if inner and _last(inner) in _JIT_NAMES:
            return _last(inner)
    return None


def _pallas_kernel_arg(call: ast.Call):
    """The kernel-body argument of a pallas_call (direct or partial form)."""
    name = _dotted(call.func) or ""
    if _last(name) == "partial":
        return call.args[1] if len(call.args) > 1 else None
    return call.args[0] if call.args else None


def _is_cache_decorated(fn) -> bool:
    for dec in fn.decorator_list:
        name = _dotted(dec if not isinstance(dec, ast.Call) else dec.func)
        if name and _last(name) in _CACHE_DECOS:
            return True
    return False


def _is_jit_decorated(fn) -> bool:
    """@jax.jit / @functools.partial(jax.jit, ...): the OUTER jit caches
    the trace, so constructions inside the body are per-trace, not
    per-call."""
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            if _jit_kind(dec) is not None:
                return True
        else:
            name = _dotted(dec)
            if name and _last(name) in _JIT_NAMES:
                return True
    return False


def _is_array_constant(value) -> bool:
    if not isinstance(value, ast.Call):
        return False
    name = _dotted(value.func)
    if not name:
        return False
    parts = name.split(".")
    return parts[-1] in _ARRAY_FNS and parts[0] in _ARRAY_MODULES


def _int_tuple(node) -> tuple:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, ast.Tuple):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, int))
    return ()


def _str_tuple(node) -> tuple:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, ast.Tuple):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


def _static_spec(call: ast.Call):
    """(static positions, static names) of a jit construction, or None."""
    if _jit_kind(call) != "jit":
        return None
    pos, names = (), ()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            pos = _int_tuple(kw.value)
        elif kw.arg == "static_argnames":
            names = _str_tuple(kw.value)
    if pos or names:
        return (frozenset(pos), frozenset(names))
    return None


def _pairs_literal(node) -> set:
    """TRACE_RUNNER_CACHES literal -> {(builder, cache), ...}."""
    out = set()
    if isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, (ast.Tuple, ast.List)) and len(e.elts) == 2:
                a, b = e.elts
                if (isinstance(a, ast.Constant) and isinstance(a.value, str)
                        and isinstance(b, ast.Constant)
                        and isinstance(b.value, str)):
                    out.add((a.value, b.value))
    return out


# ---------------------------------------------------------------------------
# per-module walk
# ---------------------------------------------------------------------------

class _Walker:
    """Collects function defs, jit-construction sites and pallas sites,
    each with its stack of enclosing FunctionDefs. Decorators are walked
    with the ENCLOSING stack (they evaluate in the outer scope)."""

    def __init__(self):
        self.defs: list = []          # (node, stack tuple)
        self.jit_sites: list = []     # (call, kind, stack tuple)
        self.pallas_sites: list = []  # (call, stack tuple)

    def walk(self, node, stack=()):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                self.walk(dec, stack)
            for default in list(node.args.defaults) + [
                    d for d in node.args.kw_defaults if d is not None]:
                self.walk(default, stack)
            self.defs.append((node, stack))
            inner = stack + (node,)
            for child in node.body:
                self.walk(child, inner)
            return
        if isinstance(node, ast.Call):
            kind = _jit_kind(node)
            if kind is not None:
                self.jit_sites.append((node, kind, stack))
                if kind == "pallas_call":
                    self.pallas_sites.append((node, stack))
        for child in ast.iter_child_nodes(node):
            self.walk(child, stack)


def _module_toplevel(tree):
    """(module names, array-const names, declared cache pairs, declared
    jit roots) from the module's top-level statements."""
    names, array_consts = set(), set()
    declared_caches, declared_roots = set(), ()
    for node in tree.body:
        targets, value = [], None
        if isinstance(node, ast.Assign):
            targets = [t for t in node.targets if isinstance(t, ast.Name)]
            value = node.value
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                            ast.Name):
            targets, value = [node.target], node.value
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            names.add(node.name)
        for t in targets:
            names.add(t.id)
            if value is not None and _is_array_constant(value):
                array_consts.add(t.id)
            if t.id == "TRACE_RUNNER_CACHES" and value is not None:
                declared_caches = _pairs_literal(value)
            if t.id == "TRACE_JIT_ROOTS" and value is not None:
                declared_roots = _str_tuple(value)
    return names, array_consts, declared_caches, declared_roots


def _store_names(fn, mod_names, _cache={}) -> frozenset:
    """Module-level dict names this function's subtree subscript-stores
    into (`_RUNNERS[key] = fn` — the runner-cache discipline). Memoised
    on the node itself, not its id(): a freed tree's ids are reused by the
    next parse, and a stale hit reads as another module's cache."""
    hit = _cache.get(fn)
    if hit is not None:
        return hit
    out = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id in mod_names):
                    out.add(t.value.id)
    out = frozenset(out)
    _cache[fn] = out
    return out


def _rel(path: str) -> str:
    ap = os.path.abspath(path)
    if ap.startswith(_REPO + os.sep):
        return os.path.relpath(ap, _REPO)
    return os.path.basename(ap)


def default_files() -> list:
    out = []
    for d in SCAN_DIRS:
        base = os.path.join(_PKG, d)
        for fn in sorted(os.listdir(base)):
            if fn.endswith(".py"):
                out.append(os.path.join(base, fn))
    return out


def _collect_statics(tree, registry: dict):
    """Phase A: {entry-point name -> (static positions, static names)} from
    jit-with-statics decorators and `name = jax.jit(f, static_...)`."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    spec = _static_spec(dec)
                    if spec is not None:
                        registry[node.name] = spec
        elif isinstance(node, ast.Assign) and isinstance(node.value,
                                                         ast.Call):
            spec = _static_spec(node.value)
            if spec is not None:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        registry[t.id] = spec


def _unhashable_desc(node) -> str | None:
    if isinstance(node, _UNHASHABLE_NODES):
        return type(node).__name__.lower()
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name and _last(name) in _UNHASHABLE_CTORS:
            return f"{_last(name)}(...)"
    return None


def _scan_file(path: str, tree, statics: dict) -> list:
    rel = _rel(path)
    mod = os.path.basename(path)[:-3]
    names, array_consts, declared_caches, declared_roots = \
        _module_toplevel(tree)
    w = _Walker()
    w.walk(tree)
    findings = []

    # ---- TC-FRESH-JIT -----------------------------------------------------
    def exempt(stack) -> bool:
        return any(_is_cache_decorated(f) or _is_jit_decorated(f)
                   or _store_names(f, names) for f in stack)

    seen = set()
    for call, kind, stack in w.jit_sites:
        if not stack or exempt(stack):
            continue
        qual = ".".join(f.name for f in stack)
        if (qual, kind) in seen:
            continue
        seen.add((qual, kind))
        findings.append(Finding(
            "trace", "TC-FRESH-JIT", Severity.ERROR, rel, f"{mod}:{qual}",
            f"{kind} constructed inside `{qual}` (line {call.lineno}) with "
            f"no caching discipline: every call re-traces and re-lowers the "
            f"program (the multichip rc=124 class). Hoist to module level, "
            f"memoize the builder, or store the jitted object in a "
            f"module-level runner cache keyed on the static params.",
            key=f"TC-FRESH-JIT:{rel}:{qual}:{kind}"))

    # ---- TC-CONST-CAPTURE -------------------------------------------------
    for call, stack in w.pallas_sites:
        karg = _pallas_kernel_arg(call)
        if not isinstance(karg, ast.Name):
            continue
        # resolve the kernel def: deepest def on the call's scope chain,
        # else module level
        kdef, kstack = None, ()
        for node, dstack in w.defs:
            if node.name != karg.id:
                continue
            if dstack == stack[:len(dstack)] and (
                    kdef is None or len(dstack) > len(kstack)):
                kdef, kstack = node, dstack
        if kdef is None:
            continue
        visible = set(array_consts)
        for f in kstack:  # closure bindings on the defining chain
            for node in ast.walk(f):
                if isinstance(node, ast.Assign) and _is_array_constant(
                        node.value):
                    visible.update(t.id for t in node.targets
                                   if isinstance(t, ast.Name))
        local = {a.arg for a in (kdef.args.args + kdef.args.posonlyargs
                                 + kdef.args.kwonlyargs)}
        local.update(n.id for n in ast.walk(kdef)
                     if isinstance(n, ast.Name)
                     and isinstance(n.ctx, (ast.Store, ast.Del)))
        for n in ast.walk(kdef):
            if (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                    and n.id in visible and n.id not in local):
                findings.append(Finding(
                    "trace", "TC-CONST-CAPTURE", Severity.ERROR, rel,
                    f"{mod}:{kdef.name}",
                    f"pallas kernel `{kdef.name}` captures the concrete "
                    f"array binding `{n.id}` from an outer scope — Pallas "
                    f"kernel bodies may not capture traced array constants "
                    f"(the PR 15 bug class); build it in-trace from scalar "
                    f"literals instead.",
                    key=f"TC-CONST-CAPTURE:{rel}:{kdef.name}:{n.id}"))
                break

    # ---- TC-UNSTABLE-STATIC -----------------------------------------------
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        if callee is None:
            continue
        spec = statics.get(_last(callee))
        if spec is None:
            continue
        pos, kwnames = spec
        for i, arg in enumerate(node.args):
            if i in pos:
                desc = _unhashable_desc(arg)
                if desc:
                    findings.append(Finding(
                        "trace", "TC-UNSTABLE-STATIC", Severity.ERROR, rel,
                        f"{mod}:{_last(callee)}",
                        f"call to `{callee}` (line {node.lineno}) passes "
                        f"{desc} at static position {i}: unhashable "
                        f"statics raise, unstable ones defeat the trace "
                        f"cache — pass a tuple / int / str.",
                        key=f"TC-UNSTABLE-STATIC:{rel}:{_last(callee)}:{i}"))
        for kw in node.keywords:
            if kw.arg in kwnames:
                desc = _unhashable_desc(kw.value)
                if desc:
                    findings.append(Finding(
                        "trace", "TC-UNSTABLE-STATIC", Severity.ERROR, rel,
                        f"{mod}:{_last(callee)}",
                        f"call to `{callee}` (line {node.lineno}) passes "
                        f"{desc} for static arg {kw.arg!r} — pass a "
                        f"hashable value.",
                        key=f"TC-UNSTABLE-STATIC:{rel}:{_last(callee)}"
                            f":{kw.arg}"))

    # ---- TC-UNCACHED-RUNNER (registry drift) ------------------------------
    def_names = {node.name for node, _ in w.defs}
    jit_fns = set()  # functions whose subtree constructs a jit
    for _call, _kind, stack in w.jit_sites:
        jit_fns.update(f.name for f in stack)
    detected = set()
    for node, _stack in w.defs:
        if node.name in jit_fns:
            for cache in _store_names(node, names):
                detected.add((node.name, cache))
    for builder, cache in sorted(detected - declared_caches):
        findings.append(Finding(
            "trace", "TC-UNCACHED-RUNNER", Severity.ERROR, rel,
            f"{mod}:{builder}",
            f"`{builder}` builds a jitted runner and caches it in "
            f"`{cache}` but is missing from this module's "
            f"TRACE_RUNNER_CACHES declaration — register it so the "
            f"retrace probes and the runner registry stay in sync.",
            key=f"TC-UNCACHED-RUNNER:{rel}:{builder}:{cache}"))
    for builder, cache in sorted(declared_caches):
        if builder not in def_names or cache not in names:
            findings.append(Finding(
                "trace", "TC-UNCACHED-RUNNER", Severity.ERROR, rel,
                f"{mod}:{builder}",
                f"TRACE_RUNNER_CACHES declares ({builder!r}, {cache!r}) "
                f"but the module no longer defines "
                f"{'that builder' if builder not in def_names else 'that cache dict'}"
                f" — stale registry entry.",
                key=f"TC-UNCACHED-RUNNER:{rel}:{builder}:{cache}:stale"))
    # module-level jitted entry points declared as lint roots
    jit_decorated = {node.name for node, stack in w.defs
                     if not stack and _is_jit_decorated(node)}
    jit_assigned = set()
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and _jit_kind(node.value) is not None:
            jit_assigned.update(t.id for t in node.targets
                                if isinstance(t, ast.Name))
    for root in declared_roots:
        if root not in jit_decorated and root not in jit_assigned:
            findings.append(Finding(
                "trace", "TC-UNCACHED-RUNNER", Severity.ERROR, rel,
                f"{mod}:{root}",
                f"TRACE_JIT_ROOTS declares {root!r} but no module-level "
                f"jitted def/assignment of that name exists — stale root.",
                key=f"TC-UNCACHED-RUNNER:{rel}:{root}:root"))
    return findings


def scan_files(paths=None) -> list:
    """Static TC-* rules over the given files (default: the ops/,
    parallel/, plonk/ scan roots)."""
    paths = list(paths) if paths is not None else default_files()
    parsed = []
    for p in paths:
        with open(p) as fh:
            parsed.append((p, ast.parse(fh.read(), filename=p)))
    statics: dict = {}
    for _p, tree in parsed:
        _collect_statics(tree, statics)
    findings = []
    for p, tree in parsed:
        findings += _scan_file(p, tree, statics)
    return findings


# ---------------------------------------------------------------------------
# TC-RETRACE-DYN: dynamic double-call probes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ProbeSpec:
    """One registered runner exercised at a tiny shape: `build()` returns
    (fn, args); fn(*args) is called twice and the SECOND call must record
    zero `backend_compile` events (compilelog capture)."""
    name: str
    file: str
    build: object


def _probe_msm():
    import jax.numpy as jnp

    from ..ops import msm as MSM
    pts = jnp.zeros((8, 3, 16), jnp.uint32)
    sc = jnp.zeros((8, 16), jnp.uint32)

    # c=2 / nbits=4: the smallest statics that still exercise the full
    # windows->combine pipeline (compile cost scales with bucket count)
    def run(p, s):
        return MSM.combine_windows(MSM.msm_windows_bits(p, s, 2, 4), 2)

    return run, (pts, sc)


def _probe_ntt():
    import jax.numpy as jnp

    from ..fields import bn254
    from ..ops import ntt as NTT
    om = bn254.fr_root_of_unity(4)
    a = jnp.zeros((16, 16), jnp.uint32)

    def run(x):
        return NTT.ntt(x, om)

    return run, (a,)


def _probe_sharded_msm():
    import importlib

    import jax.numpy as jnp

    from ..parallel.plan import current_plan
    # the package re-exports the sharded_msm FUNCTION under the module's
    # name; import the module explicitly (same idiom as plonk/backend)
    SM = importlib.import_module("spectre_tpu.parallel.sharded_msm")
    plan = current_plan()
    n = plan.pad_rows(8)
    pts = plan.place(jnp.zeros((n, 3, 16), jnp.uint32), plan.point_spec)
    sc = plan.place(jnp.zeros((n, 16), jnp.uint32), plan.scalar_spec)

    def run(p, s):
        return SM.sharded_msm(p, s, 2, plan.mesh, nbits=4, plan=plan)

    return run, (pts, sc)


def _probe_sharded_fixed():
    import importlib

    import jax.numpy as jnp

    from ..parallel.plan import current_plan
    SM = importlib.import_module("spectre_tpu.parallel.sharded_msm")
    plan = current_plan()
    n = plan.pad_rows(8)
    nwin = (4 + 2) // 2  # signed windows at c=2 / nbits=4
    pts = plan.place(jnp.zeros((n, 3, 16), jnp.uint32), plan.point_spec)
    sc = plan.place(jnp.zeros((n, 16), jnp.uint32), plan.scalar_spec)
    ng = plan.place(jnp.zeros((n,), bool), plan.sign_spec)

    def run(p, s, g):
        tab = SM.sharded_fixed_table(p, 2, nwin, plan,
                                     base_key=("trace-probe", n))
        return SM.sharded_msm_fixed(tab, s, g, 2, plan, 4)

    return run, (pts, sc, ng)


def _probe_sharded_ntt():
    import importlib

    import jax.numpy as jnp

    from ..fields import bn254
    from ..parallel.plan import current_plan
    SN = importlib.import_module("spectre_tpu.parallel.sharded_ntt")
    plan = current_plan()
    om = bn254.fr_root_of_unity(4)
    a = jnp.zeros((16, 16), jnp.uint32)

    def run(x):
        return SN.sharded_ntt(x, om, plan.mesh, plan=plan)

    return run, (a,)


def _probe_sharded_quotient():
    import importlib

    import jax.numpy as jnp
    import numpy as np

    from ..fields import bn254
    from ..parallel.plan import current_plan
    SQ = importlib.import_module("spectre_tpu.parallel.sharded_quotient")
    plan = current_plan()
    d = plan.n_devices
    # 2^6 extended domain: Bailey 8x8, divisible by any pow2 mesh <= 8
    m, logm = 64, 6
    om = bn254.fr_root_of_unity(logm)
    g = 7  # COSET_GEN
    a = jnp.zeros((m, 16), jnp.uint32)
    stack = jnp.zeros((max(d, 2), m, 16), jnp.uint32)
    s = jnp.zeros((16,), jnp.uint32)

    def run(x, st, sc):
        # one pass through all four runner caches: eval (mul + fold),
        # roll, batch-sharded LDE, fused inverse (tables resident)
        ev = SQ._eval_runner(plan, "mul", m)(x, x)
        ev = SQ._eval_runner(plan, "fold", m)(ev, sc, x)
        r = SQ._roll_runner(plan, m, 4)(ev)
        lde = SQ._lde_runner(plan, st.shape[0], logm, om, g)(st)
        inv = SQ._inv_apply(plan, np.asarray(r), logm, om, g, (1,))
        return lde, inv

    return run, (a, stack, s)


def _probe_batch_msm():
    import jax.numpy as jnp

    from ..parallel.batch_msm import batch_msm_dp
    pts = jnp.zeros((8, 3, 16), jnp.uint32)
    sb = jnp.zeros((2, 8, 16), jnp.uint32)
    ng = jnp.zeros((2, 8), bool)

    # signed/GLV runner: the only batch path that honors a tiny nbits
    # (the unsigned runner hardwires 254-bit windows — far too slow to
    # compile inside the lint-deep budget)
    def run(p, s, g):
        return batch_msm_dp(p, s, c=2, neg_batch=g, nbits=4, signed=True)

    return run, (pts, sb, ng)


# K=6 tiny double-call contexts (the lint-deep runtime budget assumes
# exactly this scale — keep additions tiny-shape and seconds-cheap)
PROBES = [
    ProbeSpec("msm.windows+combine", "spectre_tpu/ops/msm.py", _probe_msm),
    ProbeSpec("ntt.ntt", "spectre_tpu/ops/ntt.py", _probe_ntt),
    ProbeSpec("sharded_msm.windows", "spectre_tpu/parallel/sharded_msm.py",
              _probe_sharded_msm),
    ProbeSpec("sharded_msm.fixed", "spectre_tpu/parallel/sharded_msm.py",
              _probe_sharded_fixed),
    ProbeSpec("sharded_ntt", "spectre_tpu/parallel/sharded_ntt.py",
              _probe_sharded_ntt),
    ProbeSpec("sharded_quotient", "spectre_tpu/parallel/sharded_quotient.py",
              _probe_sharded_quotient),
    ProbeSpec("batch_msm.dp", "spectre_tpu/parallel/batch_msm.py",
              _probe_batch_msm),
]


def run_probe(spec: ProbeSpec) -> list:
    """Warm call, then capture compile events around an identical second
    call: any backend_compile on call #2 means the runner re-traced."""
    from ..observability import compilelog
    compilelog.install()
    fn, args = spec.build()
    with compilelog.entry_point(f"trace_lint/{spec.name}"):
        fn(*args)  # warm the trace cache
        with compilelog.capture() as events:
            fn(*args)
    n = compilelog.summarize(events)["count"]
    if n == 0:
        return []
    return [Finding(
        "trace", "TC-RETRACE-DYN", Severity.ERROR, spec.file, spec.name,
        f"second identical call of `{spec.name}` compiled {n} new XLA "
        f"program(s): the runner is not hitting its trace cache (fresh "
        f"jit/shard_map per call, or an unstable cache key).",
        key=f"TC-RETRACE-DYN:{spec.name}")]


def run_probes(specs=None) -> list:
    findings = []
    for spec in (PROBES if specs is None else specs):
        findings += run_probe(spec)
    return findings


def lint_trace(files=None, probes=None, dynamic=True) -> list:
    """The full trace engine: static AST rules + dynamic retrace probes."""
    findings = scan_files(files)
    if dynamic:
        findings += run_probes(probes)
    findings.sort(key=lambda f: -Severity.ORDER[f.severity])
    return findings


def root_counts() -> dict:
    return {"trace_files": len(default_files()),
            "trace_probes": len(PROBES)}
