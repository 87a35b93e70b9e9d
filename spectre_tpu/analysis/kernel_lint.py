"""Engine 2: JAX kernel lint — worst-case value-bound analysis over jaxprs.

The hot device ops keep 256-bit field elements as 16-bit limbs in uint32
lanes; every multiply-accumulate is budgeted by hand ("accumulators stay
< 2^24", field_ops.py header). This engine re-derives those budgets
mechanically: each kernel is traced to a jaxpr (`jax.make_jaxpr`, no
execution), input tensors get their DECLARED limb width (16 bits for limb
tensors, not the 32 the dtype would suggest), and an abstract interpreter
propagates worst-case integer value bounds through every primitive —
including scan/while/cond bodies, iterated to their trip count or to a
fixpoint.

Rules:

  KL-OVERFLOW   error   an integer multiply/add/shift/dot whose worst-case
                        TRUE value exceeds the lane dtype's max — the limb
                        headroom bug class (wrap silently corrupts high
                        bits). A product consumed ONLY by `and` masks is
                        exempt: x*y mod 2^32 has exact low bits, so masking
                        idioms like `(t0 * n0inv) & 0xFFFF` are sound.
  KL-FLOAT      error   any floating dtype inside a field-arithmetic jaxpr
                        (field elements through float units lose limbs).
  KL-CALLBACK   error   host callback primitives inside a jitted kernel
                        (pure_callback/io_callback/debug_callback/...): a
                        device round-trip per call, and a determinism leak.
  KL-WIDTH      error   host-side limb conversion (ops/limbs.py) violating
                        its declared 16-bit limb invariant on extreme
                        inputs (numpy probe, not a trace).

Kernels that are SPECIFIED over modular lanes (sha256: u32 addition is
mod-2^32 by FIPS 180-4) register with wrap_ok=True and skip KL-OVERFLOW.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .findings import Finding, Severity

_CAP = 1 << 192          # bound ceiling: far above any flag threshold
_LOOP_ITER_CAP = 64      # max abstract iterations of a loop body

_CALLBACK_PRIMS = ("callback", "outside_call", "infeed", "outfeed")


def _is_float(dtype) -> bool:
    dt = np.dtype(dtype)
    return (dt.kind == "f" or np.issubdtype(dt, np.floating)
            or "float" in dt.name)  # ml_dtypes (bfloat16, fp8) included


def _dtype_max(dtype) -> int:
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return 1
    if np.issubdtype(dt, np.integer):
        return int(np.iinfo(dt).max)
    return _CAP  # float handled by the KL-FLOAT walk


def _cap(v: int) -> int:
    return v if v < _CAP else _CAP


class _Lint:
    """Shared state across one kernel's interpretation."""

    def __init__(self, name: str, file: str, wrap_ok: bool):
        self.name = name
        self.file = file
        self.wrap_ok = wrap_ok
        self.findings: list = []
        self._keys: set = set()

    def report(self, rule: str, detail_key: str, message: str):
        key = f"{rule}:{self.name}:{detail_key}"
        if key in self._keys:
            return
        self._keys.add(key)
        self.findings.append(Finding(
            "kernel", rule, Severity.ERROR, self.file, self.name, message,
            key=key))


def _atom_bound(atom, env):
    from jax.extend import core as jcore
    if isinstance(atom, jcore.Literal):
        v = atom.val
        arr = np.asarray(v)
        if arr.dtype == np.bool_:
            return 1
        if np.issubdtype(arr.dtype, np.integer):
            return int(arr.max()) if arr.size else 0
        return 0
    return env[atom]


def _masked_only(var, eqns):
    """True when every in-body consumer of var is a bitwise-and (the exact-
    low-bits masking idiom)."""
    used = False
    for eqn in eqns:
        if any(iv is var for iv in eqn.invars
               if not hasattr(iv, "val")):
            used = True
            if eqn.primitive.name != "and":
                return False
    return used  # an unconsumed overflow (escaping output) is not exempt


def _subjaxpr(params, *keys):
    for k in keys:
        if k in params:
            return params[k]
    return None


def _interp_jaxpr(jaxpr, consts, in_bounds, lint: _Lint, check: bool,
                  path: str = ""):
    """Abstract interpretation of one (open) jaxpr. Returns out bounds."""
    env: dict = {}
    cvals: dict = {}   # id(constvar) -> numpy value, for const-aware rules
    for v, c in zip(jaxpr.constvars, consts):
        arr = np.asarray(c)
        if np.issubdtype(arr.dtype, np.integer) or arr.dtype == np.bool_:
            env[v] = int(arr.max()) if arr.size else 0
            cvals[id(v)] = arr
        else:
            env[v] = 0  # float consts caught by the KL-FLOAT walk
    for v, b in zip(jaxpr.invars, in_bounds):
        env[v] = b

    outvar_set = {id(v) for v in jaxpr.outvars if not hasattr(v, "val")}

    for ei, eqn in enumerate(jaxpr.eqns):
        outs = _eval_eqn(eqn, ei, env, jaxpr.eqns, outvar_set, lint, check,
                         path, cvals)
        for ov, ob in zip(eqn.outvars, outs):
            env[ov] = ob
    return [_atom_bound(v, env) for v in jaxpr.outvars]


def _const_value(atom, cvals):
    """Integer numpy value of an atom when statically known, else None."""
    from jax.extend import core as jcore
    if isinstance(atom, jcore.Literal):
        arr = np.asarray(atom.val)
        return arr if np.issubdtype(arr.dtype, np.integer) else None
    return cvals.get(id(atom)) if cvals else None


def _flag(lint, check, eqn, ei, path, env, eqns, outvar_set, true_val,
          dmax, what):
    """Common KL-OVERFLOW gate: wrap-ok kernels and masked-only consumers
    are exempt."""
    if not check or lint.wrap_ok or true_val <= dmax:
        return
    ov = eqn.outvars[0]
    if _masked_only(ov, eqns) and id(ov) not in outvar_set:
        return
    lint.report(
        "KL-OVERFLOW", f"{path}{eqn.primitive.name}{ei}",
        f"{what}: worst-case value 2^{true_val.bit_length()} exceeds "
        f"{np.dtype(ov.aval.dtype).name} max (2^{dmax.bit_length()}-1) and "
        f"the result is not mask-consumed — high bits silently wrap")


def _eval_eqn(eqn, ei, env, eqns, outvar_set, lint: _Lint, check: bool,
              path: str, cvals: dict | None = None):
    prim = eqn.primitive.name
    params = eqn.params
    ins = [_atom_bound(a, env) for a in eqn.invars]
    try:
        dmax = _dtype_max(eqn.outvars[0].aval.dtype)
    except (AttributeError, TypeError):
        dmax = _CAP

    if check and any(p in prim for p in _CALLBACK_PRIMS):
        lint.report("KL-CALLBACK", f"{path}{prim}{ei}",
                    f"host callback primitive '{prim}' inside the kernel "
                    f"jaxpr: device round-trip per call")

    # --- control flow: recurse -----------------------------------------
    if prim == "scan":
        closed = params["jaxpr"]
        ncons, ncarry = params["num_consts"], params["num_carry"]
        length = int(params.get("length", 1) or 1)
        consts_b = ins[:ncons]
        carry_b = list(ins[ncons:ncons + ncarry])
        xs_b = ins[ncons + ncarry:]  # per-step slices share the array bound
        iters = min(length, _LOOP_ITER_CAP)
        converged = False
        for _ in range(iters):
            outs = _interp_jaxpr(closed.jaxpr, closed.consts,
                                 consts_b + carry_b + xs_b, lint,
                                 check=False, path=path)
            new_carry = [max(a, b) for a, b in zip(carry_b, outs[:ncarry])]
            if new_carry == carry_b:
                converged = True
                break
            carry_b = new_carry
        if not converged and length > iters:
            # trip count exceeds the abstract budget and bounds still grow:
            # widen to dtype max and skip checks inside (no false accusals)
            carry_b = [_CAP for _ in carry_b]
            outs = _interp_jaxpr(closed.jaxpr, closed.consts,
                                 consts_b + carry_b + xs_b, lint,
                                 check=False, path=path)
        else:
            outs = _interp_jaxpr(closed.jaxpr, closed.consts,
                                 consts_b + carry_b + xs_b, lint,
                                 check=check, path=path + f"scan{ei}/")
        return outs[:ncarry] + outs[ncarry:]

    if prim == "while":
        cond_j, body_j = params["cond_jaxpr"], params["body_jaxpr"]
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry_b = list(ins[cn + bn:])
        converged = False
        for _ in range(16):
            _interp_jaxpr(cond_j.jaxpr, cond_j.consts,
                          cond_consts + carry_b, lint, check=False, path=path)
            outs = _interp_jaxpr(body_j.jaxpr, body_j.consts,
                                 body_consts + carry_b, lint, check=False,
                                 path=path)
            new_carry = [max(a, b) for a, b in zip(carry_b, outs)]
            if new_carry == carry_b:
                converged = True
                break
            carry_b = new_carry
        if converged:
            _interp_jaxpr(body_j.jaxpr, body_j.consts, body_consts + carry_b,
                          lint, check=check, path=path + f"while{ei}/")
        else:
            carry_b = [_CAP for _ in carry_b]
        return carry_b

    if prim == "cond":
        branches = params["branches"]
        op_ins = ins[1:]
        outs = None
        for bi, br in enumerate(branches):
            bouts = _interp_jaxpr(br.jaxpr, br.consts, op_ins, lint,
                                  check=check, path=path + f"cond{ei}.{bi}/")
            outs = bouts if outs is None else \
                [max(a, b) for a, b in zip(outs, bouts)]
        return outs

    closed = _subjaxpr(params, "jaxpr", "call_jaxpr", "fun_jaxpr")
    if closed is not None and hasattr(closed, "jaxpr"):
        return _interp_jaxpr(closed.jaxpr, closed.consts, ins, lint,
                             check=check, path=path + f"{prim}{ei}/")

    # --- arithmetic ----------------------------------------------------
    if prim == "mul":
        true = ins[0] * ins[1]
        _flag(lint, check, eqn, ei, path, env, eqns, outvar_set, true, dmax,
              f"integer multiply of bounds 2^{ins[0].bit_length()} x "
              f"2^{ins[1].bit_length()}")
        return [_cap(min(true, dmax))]
    if prim == "add":
        true = ins[0] + ins[1]
        _flag(lint, check, eqn, ei, path, env, eqns, outvar_set, true, dmax,
              "integer add-chain")
        return [_cap(min(true, dmax))]
    if prim == "sub":
        # signed a-b stays within max(|a|,|b|) magnitude (negative results
        # are representable, no wrap); unsigned wrap-to-borrow is a
        # deliberate idiom (field_ops._sub_limbs: `(a - b - borrow) & MASK`
        # on uint32 limbs, the borrows themselves resolved beside it) —
        # conservatively full-width there, recovered by downstream masks
        try:
            if np.issubdtype(np.dtype(eqn.outvars[0].aval.dtype),
                             np.signedinteger):
                return [max(ins)]
        except (AttributeError, TypeError):
            pass
        return [dmax]
    if prim == "dot_general":
        dims = params.get("dimension_numbers")
        k = 1
        try:
            (lc, _rc), _ = dims
            for d in lc:
                k *= eqn.invars[0].aval.shape[d]
        except Exception:
            k = max(eqn.invars[0].aval.size, 1)
        # MXU accumulation dtype: `preferred_element_type` names the
        # systolic-array accumulator (int8 x int8 -> int32 on TPU); the
        # overflow budget is the ACCUMULATOR's, not the operand lanes'.
        # Absent the param, the output dtype is the accumulator (XLA
        # accumulates wider internally but wraps on store — which is
        # exactly the silent-wrap this rule exists to catch).
        acc_dt = params.get("preferred_element_type")
        acc_max = _dtype_max(acc_dt) if acc_dt is not None else dmax
        acc_name = np.dtype(acc_dt).name if acc_dt is not None \
            else np.dtype(eqn.outvars[0].aval.dtype).name
        true = ins[0] * ins[1] * k
        # const-operand refinement: when one side is a statically known
        # integer matrix (one-hot conv reductions, DFT twiddle tables), the
        # true per-output-entry bound is other_bound * max column |sum| of
        # the const over ITS contraction dims — for the one-hot [1024, 63]
        # convolution matrix that is other_bound * L8 (32), not
        # other_bound * 1024, which is what PROVES the C*L*255^2 int32
        # column bound of the matmul-NTT short transform
        try:
            (lc, rc), _ = dims
            for idx, cdims in ((0, tuple(lc)), (1, tuple(rc))):
                arr = _const_value(eqn.invars[idx], cvals)
                if arr is None or not cdims:
                    continue
                colsum = int(np.abs(arr.astype(np.int64)).sum(
                    axis=cdims).max()) if arr.size else 0
                true = min(true, ins[1 - idx] * colsum)
        except Exception:
            pass
        _flag(lint, check, eqn, ei, path, env, eqns, outvar_set, true,
              acc_max,
              f"dot_general accumulating {k} products in {acc_name} "
              f"(MXU accumulator)")
        return [_cap(min(true, acc_max, dmax))]
    if prim == "reduce_sum":
        try:
            k = max(eqn.invars[0].aval.size
                    // max(eqn.outvars[0].aval.size, 1), 1)
        except Exception:
            k = 1
        true = ins[0] * k
        _flag(lint, check, eqn, ei, path, env, eqns, outvar_set, true, dmax,
              f"reduce_sum over {k} lanes")
        return [_cap(min(true, dmax))]
    if prim == "integer_pow":
        y = params.get("y", 2)
        true = _cap(max(ins[0], 1) ** abs(y)) if y >= 0 else dmax
        _flag(lint, check, eqn, ei, path, env, eqns, outvar_set, true, dmax,
              f"integer_pow^{y}")
        return [_cap(min(true, dmax))]
    if prim == "shift_left":
        from jax.extend import core as jcore
        s_atom = eqn.invars[1]
        if isinstance(s_atom, jcore.Literal):
            s = int(np.asarray(s_atom.val).max())
            true = ins[0] << s
            _flag(lint, check, eqn, ei, path, env, eqns, outvar_set, true,
                  dmax, f"shift_left by {s}")
            return [_cap(min(true, dmax))]
        return [dmax]  # data-dependent shift: cannot prove overflow
    if prim in ("shift_right_logical", "shift_right_arithmetic"):
        from jax.extend import core as jcore
        s_atom = eqn.invars[1]
        if isinstance(s_atom, jcore.Literal):
            return [ins[0] >> int(np.asarray(s_atom.val).min())]
        return [ins[0]]
    if prim == "and":
        return [min(ins)]
    if prim in ("or", "xor"):
        bits = max(b.bit_length() for b in ins)
        return [min((1 << bits) - 1, dmax)]
    if prim == "not":
        return [dmax]
    if prim == "rem":
        from jax.extend import core as jcore
        if isinstance(eqn.invars[1], jcore.Literal):
            return [min(ins[0], max(ins[1] - 1, 0))]
        return [ins[0]]
    if prim == "div":
        return [ins[0]]
    if prim in ("max", "min"):
        return [max(ins)] if prim == "max" else [min(ins)]
    if prim == "abs":
        # the bound tracks worst-case magnitude, and sub on signed lanes
        # already returns max(|a|,|b|) — abs preserves that magnitude
        # (signed-digit MSM: |digit| <= 2^(c-1), not int32 max)
        return [max(ins)]
    if prim == "neg":
        try:
            if np.issubdtype(np.dtype(eqn.outvars[0].aval.dtype),
                             np.signedinteger):
                return [max(ins)]
        except (AttributeError, TypeError):
            pass
        return [dmax]     # unsigned negation wraps
    if prim == "clamp":
        return [min(ins[1], ins[2])]
    if prim in ("eq", "ne", "lt", "le", "gt", "ge", "reduce_and",
                "reduce_or"):
        return [1 for _ in eqn.outvars]
    if prim == "iota":
        try:
            d = params.get("dimension", 0)
            return [max(eqn.outvars[0].aval.shape[d] - 1, 0)]
        except Exception:
            return [dmax]
    if prim in ("argmax", "argmin"):
        return [max(eqn.invars[0].aval.size - 1, 0)]
    if prim in ("reduce_max", "reduce_min"):
        return [ins[0]]
    if prim == "select_n":
        return [max(ins[1:]) if len(ins) > 1 else ins[0]]
    if prim == "concatenate":
        return [max(ins)]
    if prim == "pad":
        return [max(ins)]
    if prim == "sort":
        nout = len(eqn.outvars)
        return ins[:nout] if len(ins) >= nout else [max(ins)] * nout
    if prim in ("scatter", "scatter_max", "scatter-max"):
        return [max(ins[0], ins[-1])]
    if prim in ("scatter_add", "scatter-add"):
        upd = eqn.invars[-1].aval.size if hasattr(eqn.invars[-1], "aval") \
            else 1
        return [_cap(min(ins[0] + ins[-1] * max(upd, 1), dmax))]
    if prim == "convert_element_type":
        return [min(ins[0], dmax)]
    if prim in ("broadcast_in_dim", "reshape", "squeeze", "expand_dims",
                "transpose", "slice", "rev", "copy", "stop_gradient",
                "gather", "dynamic_slice", "device_put", "real", "imag",
                "reduce_precision"):
        return [ins[0]] + [dmax] * (len(eqn.outvars) - 1)
    if prim == "dynamic_update_slice":
        return [max(ins[0], ins[1])]

    # unknown primitive: conservative full-width outputs, never a finding
    return [dmax for _ in eqn.outvars]


def _walk_float_and_callbacks(jaxpr, lint: _Lint, path: str = ""):
    """KL-FLOAT: any floating dtype among eqn inputs/outputs/consts."""
    for ei, eqn in enumerate(jaxpr.eqns):
        for atom in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(atom, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and _is_float(dt):
                lint.report(
                    "KL-FLOAT", f"{path}{eqn.primitive.name}{ei}",
                    f"float dtype {np.dtype(dt).name} flows through "
                    f"'{eqn.primitive.name}' — field arithmetic must stay "
                    f"integral (rounding destroys limbs)")
                break
        for p in eqn.params.values():
            sub = p if hasattr(p, "jaxpr") else None
            if sub is not None:
                _walk_float_and_callbacks(sub.jaxpr, lint,
                                          path + f"{eqn.primitive.name}{ei}/")
            elif isinstance(p, (tuple, list)):
                for q in p:
                    if hasattr(q, "jaxpr"):
                        _walk_float_and_callbacks(
                            q.jaxpr, lint,
                            path + f"{eqn.primitive.name}{ei}/")


def lint_fn(fn, args, *, name: str, file: str, in_bits=16,
            wrap_ok: bool = False) -> list:
    """Trace fn(*args) to a jaxpr and lint it. in_bits: declared input
    value width — an int for all array inputs, or a list per flattened
    input. The declared width is the analysis ROOT: 16-bit limb tensors in
    uint32 lanes start at 2^16-1, not the dtype's 2^32-1."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    lint = _Lint(name, file, wrap_ok)
    invars = closed.jaxpr.invars
    if isinstance(in_bits, int):
        bits_list = [in_bits] * len(invars)
    else:
        bits_list = list(in_bits)
        assert len(bits_list) == len(invars), \
            f"{name}: {len(bits_list)} declared widths for {len(invars)} inputs"
    in_bounds = []
    for v, bits in zip(invars, bits_list):
        dm = _dtype_max(v.aval.dtype)
        in_bounds.append(min((1 << bits) - 1, dm))
    for c in closed.consts:
        arr = np.asarray(c)
        if _is_float(arr.dtype):
            lint.report("KL-FLOAT", "const",
                        f"float constant of dtype {arr.dtype} captured by "
                        f"the kernel trace")
    _interp_jaxpr(closed.jaxpr, closed.consts, in_bounds, lint, check=True)
    _walk_float_and_callbacks(closed.jaxpr, lint)
    return lint.findings


# ---------------------------------------------------------------------------
# kernel registry: the hot ops, traced at small shapes
# ---------------------------------------------------------------------------

@dataclass
class KernelSpec:
    name: str
    file: str
    build: object            # () -> (fn, args)
    in_bits: object = 16     # declared width(s) of the flattened inputs
    wrap_ok: bool = False    # mod-2^width lanes are the SPEC (sha256)


def _u32(shape, fill=0):
    return np.zeros(shape, dtype=np.uint32) + np.uint32(fill)


def _field_pair():
    import jax.numpy as jnp
    a = jnp.asarray(_u32((4, 16)))
    b = jnp.asarray(_u32((4, 16)))
    return a, b


def _build_field(op):
    def build():
        from ..ops import field_ops as F
        ctx = F.fr_ctx()
        a, b = _field_pair()
        if op in ("add", "sub", "mont_mul"):
            return (lambda x, y: getattr(F, op)(ctx, x, y)), (a, b)
        return (lambda x: getattr(F, op)(ctx, x)), (a,)
    return build


def _build_ntt(inverse=False):
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        omega = Domain(3).omega
        a = jnp.asarray(_u32((8, 16)))
        # trace the unjitted kernel core (a jitted wrapper would lint as an
        # opaque pjit call) at the radix2 default
        if inverse:
            return (lambda x: NTT._inv_kernel.__wrapped__(
                x, omega, None, False, "radix2")), (a,)
        return (lambda x: NTT._fwd_kernel.__wrapped__(
            x, omega, None, "radix2")), (a,)
    return build


def _build_ntt_many(inverse=False):
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        omega = Domain(3).omega
        a = jnp.asarray(_u32((2, 8, 16)))       # [B, n, 16] column stack
        if inverse:
            return (lambda x: NTT._inv_kernel.__wrapped__(
                x, omega, None, False, "radix2")), (a,)
        return (lambda x: NTT._fwd_kernel.__wrapped__(
            x, omega, None, "radix2")), (a,)
    return build


def _build_ntt_fourstep():
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        omega = Domain(4).omega                 # n=16 -> 4x4 Bailey split
        a = jnp.asarray(_u32((2, 16, 16)))
        return (lambda x: NTT._fwd_kernel.__wrapped__(
            x, omega, None, "fourstep")), (a,)
    return build


def _build_coset_lde(mode):
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        omega = Domain(4).omega
        a = jnp.asarray(_u32((2, 16, 16)))
        # the fused coset-LDE entry: std->mont + g^i scale in stage 0
        return (lambda x: NTT._fwd_kernel.__wrapped__(
            x, omega, ("std", 7), mode)), (a,)
    return build


def _build_coset_intt_std():
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        omega = Domain(4).omega
        a = jnp.asarray(_u32((2, 16, 16)))
        # fused inverse: iNTT + combined g^{-i}·n^{-1} + mont->std table
        return (lambda x: NTT._inv_kernel.__wrapped__(
            x, omega, 7, True, "radix2")), (a,)
    return build


def _build_ntt_fourstep_matmul():
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        omega = Domain(4).omega                 # n=16 -> 4x4 matmul legs
        a = jnp.asarray(_u32((2, 16, 16)))
        return (lambda x: NTT._fwd_kernel.__wrapped__(
            x, omega, None, "fourstep", "matmul")), (a,)
    return build


def _build_dft_matmul():
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        # n=64 is the smallest length where the naive dot_general estimate
        # (n·255² · 1024 one-hot products) exceeds int32 — the const-colsum
        # refinement must PROVE the true C·L·255² column bound here
        omega = Domain(6).omega
        a = jnp.asarray(_u32((64, 16)))
        return (lambda x: NTT._ntt_dft_matmul(x, 6, omega)), (a,)
    return build


def _build_dft_matmul_split():
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        # the two-level carry split (n > 1024 production path) traced at a
        # lintable length by forcing the group width: each group's one-hot
        # slice has column |sum| ≤ 4, so the refinement proves the grouped
        # bound n·255²·W — the same structure `lint_matmul_cap` scales to
        # the shipped _MATMUL_MAX_LOGN analytically
        omega = Domain(6).omega
        a = jnp.asarray(_u32((64, 16)))
        return (lambda x: NTT._ntt_dft_matmul(x, 6, omega,
                                              group_width=4)), (a,)
    return build


def _build_coset_intt_std_vinv():
    def build():
        import jax.numpy as jnp
        from ..ops import ntt as NTT
        from ..plonk.domain import Domain
        dom = Domain(2)                         # n_ext = 16
        a = jnp.asarray(_u32((2, 16, 16)))
        # the folded quotient inverse: vanishing-inverse period tuple as
        # the stage-0 pre-scale (real Domain values, as the prover passes)
        return (lambda x: NTT._inv_kernel.__wrapped__(
            x, dom.omega_ext, 7, True, "radix2", "stages",
            dom.vanishing_inv_period_vals())), (a,)
    return build


def _build_glv_device():
    def build():
        import jax.numpy as jnp
        from ..ops import glv
        # [n, 16] full-scalar 16-bit limbs; the Barrett floor-division and
        # two's-complement residual scans must stay inside uint32 (the
        # CIOS-shaped _mul_const accumulator bound is < 2^22)
        sc = jnp.asarray(_u32((4, 16)))
        return (lambda s: glv.decompose_device.__wrapped__(s)), (sc,)
    return build


def _build_msm():
    import jax.numpy as jnp
    from ..ops import msm as M
    pts = jnp.asarray(_u32((8, 3, 16)))
    sc = jnp.asarray(_u32((8, 16)))
    return (lambda p, s: M.msm_windows.__wrapped__(p, s, 4)), (pts, sc)


def _build_msm_combine():
    import jax.numpy as jnp
    from ..ops import msm as M
    wins = jnp.asarray(_u32((64, 3, 16)))
    return (lambda w: M.combine_windows.__wrapped__(w, 4)), (wins,)


def _build_signed_digits():
    import jax.numpy as jnp
    from ..ops import msm as M
    sc = jnp.asarray(_u32((8, 8)))      # GLV half-scalar magnitudes
    return (lambda s: M.signed_digit_stream(s, 4, 32)), (sc,)


def _build_msm_signed():
    import jax.numpy as jnp
    from ..ops import msm as M
    pts = jnp.asarray(_u32((8, 3, 16)))
    sc = jnp.asarray(_u32((8, 8)))
    neg = jnp.zeros(8, dtype=bool)
    return (lambda p, s, g:
            M.msm_windows_signed.__wrapped__(p, s, g, 4, 126)), (pts, sc, neg)


def _build_msm_fixed():
    import jax.numpy as jnp
    from ..ops import msm as M
    c, nbits, n2 = 8, 126, 4
    nwin = (nbits + c) // c
    table = jnp.asarray(_u32((nwin, n2, 3, 16)))
    sc = jnp.asarray(_u32((n2, 8)))
    neg = jnp.zeros(n2, dtype=bool)
    return (lambda t, s, g:
            M.msm_fixed_run.__wrapped__(t, s, g, c, nbits)), (table, sc, neg)


def _build_msm_bits():
    import jax.numpy as jnp
    from ..ops import msm as M
    pts = jnp.asarray(_u32((8, 3, 16)))
    sc = jnp.asarray(_u32((8, 8)))      # GLV half-scalar width
    return (lambda p, s:
            M.msm_windows_bits.__wrapped__(p, s, 4, 126)), (pts, sc)


def _build_endo():
    import jax.numpy as jnp
    from ..ops import ec as E
    pts = jnp.asarray(_u32((8, 3, 16)))
    return (lambda p: E.endo(p)), (pts,)


# --- mesh-sharded kernels (ISSUE 13) ---------------------------------------
# The SPMD programs are shard_map closures (opaque to this tracer), so the
# per-shard LOCAL bodies are extracted as module functions in
# parallel/sharded_msm.py / sharded_ntt.py and traced here exactly as a
# single shard sees them: widx stands in for lax.axis_index, collectives
# (all_gather/all_to_all) happen outside these roots and move data only.

def _build_sharded_fold():
    import jax.numpy as jnp
    from ..parallel.sharded_msm import _fold_points
    stacked = jnp.asarray(_u32((4, 2, 3, 16)))
    return (lambda s: _fold_points(s)), (stacked,)


def _build_sharded_windows_signed():
    import jax.numpy as jnp
    from ..parallel.sharded_msm import _shard_windows_signed
    pts = jnp.asarray(_u32((8, 3, 16)))
    sc = jnp.asarray(_u32((8, 8)))      # GLV half-scalar magnitudes
    neg = jnp.zeros(8, dtype=bool)
    widx = jnp.uint32(0)
    # c=4 / 32 windows, one window shard (nloc == nwin_padded == nwin)
    return (lambda p, s, g, w: _shard_windows_signed(
        p, s, g, w, 4, 32, 32, 32, (1 << 3) + 1)), (pts, sc, neg, widx)


def _build_sharded_windows_unsigned():
    import jax.numpy as jnp
    from ..parallel.sharded_msm import _shard_windows_unsigned
    pts = jnp.asarray(_u32((8, 3, 16)))
    sc = jnp.asarray(_u32((8, 16)))     # full 254-bit scalars
    widx = jnp.uint32(0)
    return (lambda p, s, w: _shard_windows_unsigned(
        p, s, w, 4, 8, 8, 8, 1 << 4)), (pts, sc, widx)


def _build_sharded_fixed():
    import jax.numpy as jnp
    from ..parallel.sharded_msm import _shard_fixed_local
    c, nwin, n2 = 8, 16, 4
    table = jnp.asarray(_u32((nwin, n2, 3, 16)))
    sc = jnp.asarray(_u32((n2, 8)))
    neg = jnp.zeros(n2, dtype=bool)
    widx = jnp.uint32(0)
    return (lambda t, s, g, w: _shard_fixed_local(
        t, s, g, w, c, nwin, nwin, nwin, (1 << (c - 1)) + 1)), \
        (table, sc, neg, widx)


def _build_sharded_table():
    import jax.numpy as jnp
    from ..parallel.sharded_msm import _build_table_local
    pts = jnp.asarray(_u32((4, 3, 16)))
    # tiny chains (c=2, 4 windows, padded to 8) — bounds don't depend on
    # the doubling-chain length
    return (lambda p: _build_table_local(p, 2, 4, 8)), (pts,)


def _build_sharded_ntt_rows():
    def build():
        import jax.numpy as jnp
        from ..parallel.sharded_ntt import _rows_local
        from ..plonk.domain import Domain
        omega_row = Domain(3).omega
        block = jnp.asarray(_u32((4, 8, 16)))
        twb = jnp.asarray(_u32((4, 8, 16)))
        return (lambda b, t: _rows_local(b, t, omega_row, "radix2")), \
            (block, twb)
    return build


def _build_sharded_ntt_cols():
    def build():
        import jax.numpy as jnp
        from ..parallel.sharded_ntt import _cols_local
        from ..plonk.domain import Domain
        omega_col = Domain(3).omega
        y = jnp.asarray(_u32((4, 8, 16)))
        return (lambda b: _cols_local(b, omega_col, "radix2")), (y,)
    return build


# --- mesh-sharded quotient (ISSUE 19): per-shard bodies of the sharded
# LDE prefetch and the fused inverse boundary, traced single-shard like the
# sharded NTT above (the pointwise eval/roll runners contain only field_ops
# primitives and ppermute/concat — nothing beyond roots already covered).

def _build_sharded_quotient_lde():
    def build():
        import jax.numpy as jnp
        from ..parallel.sharded_quotient import _lde_local
        from ..plonk.domain import COSET_GEN, Domain
        omega = Domain(3).omega
        stack = jnp.asarray(_u32((2, 8, 16)))   # std-form columns
        return (lambda s: _lde_local(s, omega, COSET_GEN, "radix2",
                                     "stages")), (stack,)
    return build


def _build_sharded_quotient_inv_rows():
    def build():
        import jax.numpy as jnp
        from ..fields import bn254
        from ..parallel.sharded_quotient import _inv_rows_local
        from ..plonk.domain import Domain
        omega_row = pow(Domain(3).omega, -1, bn254.R)
        block = jnp.asarray(_u32((4, 8, 16)))
        scb = jnp.asarray(_u32((4, 8, 16)))     # vinv stage-0 pre-scale
        twb = jnp.asarray(_u32((4, 8, 16)))
        return (lambda b, s, t: _inv_rows_local(
            b, s, t, omega_row, "radix2", "stages")), (block, scb, twb)
    return build


def _build_sharded_quotient_inv_cols():
    def build():
        import jax.numpy as jnp
        from ..fields import bn254
        from ..parallel.sharded_quotient import _inv_cols_local
        from ..plonk.domain import Domain
        omega_col = pow(Domain(3).omega, -1, bn254.R)
        y = jnp.asarray(_u32((4, 8, 16)))
        outb = jnp.asarray(_u32((4, 8, 16)))    # raw combined out table
        return (lambda b, o: _inv_cols_local(
            b, o, omega_col, "radix2", "stages")), (y, outb)
    return build


def _build_field_mxu():
    def build():
        from ..ops import field_mxu as M
        from ..ops import field_ops as F
        ctx = F.fr_ctx()
        a, b = _field_pair()
        return (lambda x, y: M.mont_mul(ctx, x, y)), (a, b)
    return build


def _build_poseidon():
    import jax.numpy as jnp
    from ..ops import poseidon as P
    state = jnp.asarray(_u32((2, P.T, 16)))
    return (lambda s: P.permute(s)), (state,)


def _build_sha_compress():
    import jax.numpy as jnp
    from ..ops import sha256 as S
    state = jnp.asarray(_u32((2, 8)))
    blocks = jnp.asarray(_u32((2, 16)))
    return (lambda st, bl: S.compress(st, bl)), (state, blocks)


def _build_sha_pairs():
    import jax.numpy as jnp
    from ..ops import sha256 as S
    left = jnp.asarray(_u32((2, 8)))
    right = jnp.asarray(_u32((2, 8)))
    return (lambda l_, r_: S.hash_pairs(l_, r_)), (left, right)


KERNELS = [
    KernelSpec("field_ops.add", "spectre_tpu/ops/field_ops.py",
               _build_field("add")),
    KernelSpec("field_ops.sub", "spectre_tpu/ops/field_ops.py",
               _build_field("sub")),
    KernelSpec("field_ops.mont_mul", "spectre_tpu/ops/field_ops.py",
               _build_field("mont_mul")),
    KernelSpec("field_ops.neg", "spectre_tpu/ops/field_ops.py",
               _build_field("neg")),
    KernelSpec("field_ops.to_mont", "spectre_tpu/ops/field_ops.py",
               _build_field("to_mont")),
    KernelSpec("field_ops.inv", "spectre_tpu/ops/field_ops.py",
               _build_field("inv")),
    KernelSpec("ntt.ntt", "spectre_tpu/ops/ntt.py", _build_ntt(False)),
    KernelSpec("ntt.intt", "spectre_tpu/ops/ntt.py", _build_ntt(True)),
    # batched / moded NTT pipeline entry points (ISSUE 4): the [B, n, 16]
    # many-polynomial kernels, the four-step (Bailey) mode, and the fused
    # coset-LDE boundaries must stay inside the same value budgets as the
    # per-column radix-2 path they replace
    KernelSpec("ntt.ntt_many", "spectre_tpu/ops/ntt.py",
               _build_ntt_many(False)),
    KernelSpec("ntt.intt_many", "spectre_tpu/ops/ntt.py",
               _build_ntt_many(True)),
    KernelSpec("ntt.fourstep", "spectre_tpu/ops/ntt.py",
               _build_ntt_fourstep()),
    KernelSpec("ntt.coset_lde_std", "spectre_tpu/ops/ntt.py",
               _build_coset_lde("radix2")),
    KernelSpec("ntt.coset_lde_fourstep", "spectre_tpu/ops/ntt.py",
               _build_coset_lde("fourstep")),
    KernelSpec("ntt.coset_intt_std", "spectre_tpu/ops/ntt.py",
               _build_coset_intt_std()),
    # MXU-native matmul NTT (this PR): the DFT-matmul short-transform body
    # both inside the fourstep pipeline and standalone at the length where
    # the int32 column bound needs the const-colsum dot_general refinement,
    # plus the folded quotient vanishing-inverse variant of the fused iNTT
    KernelSpec("ntt.fourstep_matmul", "spectre_tpu/ops/ntt.py",
               _build_ntt_fourstep_matmul()),
    KernelSpec("ntt.dft_matmul", "spectre_tpu/ops/ntt.py",
               _build_dft_matmul()),
    KernelSpec("ntt.dft_matmul_split", "spectre_tpu/ops/ntt.py",
               _build_dft_matmul_split()),
    KernelSpec("ntt.coset_intt_std_vinv", "spectre_tpu/ops/ntt.py",
               _build_coset_intt_std_vinv()),
    # on-device GLV Babai rounding (this PR): exact Barrett floor division
    # + mod-2^144 two's-complement residuals, all in uint32 limb lanes
    KernelSpec("glv.decompose_device", "spectre_tpu/ops/glv.py",
               _build_glv_device()),
    KernelSpec("msm.msm_windows", "spectre_tpu/ops/msm.py", _build_msm),
    KernelSpec("msm.combine_windows", "spectre_tpu/ops/msm.py",
               _build_msm_combine),
    # GLV / signed-digit / fixed-base MSM entry points (PR 2): the digit
    # recode carries signed int32 lanes and the window kernels fold sign
    # masks into point negations — all must stay inside the same value
    # budgets as the vanilla path
    KernelSpec("msm.signed_digit_stream", "spectre_tpu/ops/msm.py",
               _build_signed_digits),
    KernelSpec("msm.msm_windows_signed", "spectre_tpu/ops/msm.py",
               _build_msm_signed, in_bits=[16, 16, 1]),
    KernelSpec("msm.msm_fixed_run", "spectre_tpu/ops/msm.py",
               _build_msm_fixed, in_bits=[16, 16, 1]),
    # PR 3 (fallback coverage): plain-glv mode enters via msm_windows_bits
    # at GLV half-scalar width — the one MSM entry point not yet traced
    # (the fixed->glv+signed table-budget degrade rides the already-
    # registered msm_windows_signed); register it so every mode a degraded
    # service can select stays under lint
    KernelSpec("msm.msm_windows_bits", "spectre_tpu/ops/msm.py",
               _build_msm_bits),
    KernelSpec("ec.endo", "spectre_tpu/ops/ec.py", _build_endo),
    # mesh-sharded MSM/NTT per-shard bodies (ISSUE 13): the shard_map
    # programs route ALL local math through these extracted roots, so a
    # width/float regression in the distributed path shows up here without
    # needing a device mesh in the linter
    KernelSpec("sharded_msm.fold_points",
               "spectre_tpu/parallel/sharded_msm.py", _build_sharded_fold),
    KernelSpec("sharded_msm.windows_shard_signed",
               "spectre_tpu/parallel/sharded_msm.py",
               _build_sharded_windows_signed, in_bits=[16, 16, 1, 1]),
    KernelSpec("sharded_msm.windows_shard",
               "spectre_tpu/parallel/sharded_msm.py",
               _build_sharded_windows_unsigned, in_bits=[16, 16, 1]),
    KernelSpec("sharded_msm.fixed_shard",
               "spectre_tpu/parallel/sharded_msm.py",
               _build_sharded_fixed, in_bits=[16, 16, 1, 1]),
    KernelSpec("sharded_msm.table_build_shard",
               "spectre_tpu/parallel/sharded_msm.py", _build_sharded_table),
    KernelSpec("sharded_ntt.rows_shard",
               "spectre_tpu/parallel/sharded_ntt.py",
               _build_sharded_ntt_rows()),
    KernelSpec("sharded_ntt.cols_shard",
               "spectre_tpu/parallel/sharded_ntt.py",
               _build_sharded_ntt_cols()),
    KernelSpec("sharded_quotient.lde_shard",
               "spectre_tpu/parallel/sharded_quotient.py",
               _build_sharded_quotient_lde()),
    KernelSpec("sharded_quotient.inv_rows_shard",
               "spectre_tpu/parallel/sharded_quotient.py",
               _build_sharded_quotient_inv_rows()),
    KernelSpec("sharded_quotient.inv_cols_shard",
               "spectre_tpu/parallel/sharded_quotient.py",
               _build_sharded_quotient_inv_cols()),
    # MXU int8-limb matmul field multiply (shapes stabilized; the
    # dot_general rule reads its preferred_element_type accumulator)
    KernelSpec("field_mxu.mont_mul", "spectre_tpu/ops/field_mxu.py",
               _build_field_mxu()),
    KernelSpec("poseidon.permute", "spectre_tpu/ops/poseidon.py",
               _build_poseidon),
    # SHA-256 u32 lanes are modular BY SPEC (FIPS 180-4): wrap is the
    # semantics, so only float/callback rules apply
    KernelSpec("sha256.compress", "spectre_tpu/ops/sha256.py",
               _build_sha_compress, in_bits=32, wrap_ok=True),
    KernelSpec("sha256.hash_pairs", "spectre_tpu/ops/sha256.py",
               _build_sha_pairs, in_bits=32, wrap_ok=True),
]


def lint_limbs_host() -> list:
    """KL-WIDTH probe for the host-side limb converters (numpy, untraceable):
    drive them with extreme inputs and check the declared 16-bit invariant
    plus exact round-trips. A widened limb or dropped mask shows up here."""
    import jax.numpy as jnp

    from ..fields import bn254
    from ..ops import limbs as L

    out = []
    file = "spectre_tpu/ops/limbs.py"

    def bad(detail, msg):
        out.append(Finding("kernel", "KL-WIDTH", Severity.ERROR, file,
                           "limbs.host", msg, key=f"KL-WIDTH:limbs:{detail}"))

    ones64 = np.full((3, 4), np.uint64(2**64 - 1), dtype=np.uint64)
    u16 = L.u64limbs_to_u16limbs(ones64)
    if int(u16.max()) > L.LIMB_MASK:
        bad("u64to16-mask", f"u64limbs_to_u16limbs emits limb "
            f"{int(u16.max()):#x} > declared {L.LIMB_BITS}-bit mask")
    if not np.array_equal(L.u16limbs_to_u64limbs(u16), ones64):
        bad("u64-roundtrip", "u64<->u16 limb round-trip loses bits at the "
            "all-ones extreme")
    # the wire format's device half has to give the host split's limbs
    if not np.array_equal(np.asarray(L.split_limbs16(
            jnp.asarray(L.pack_u64limbs(ones64)))), u16):
        bad("split-device", "split_limbs16 of the packed rows differs from "
            "u64limbs_to_u16limbs at the all-ones extreme")
    vals = [0, 1, bn254.R - 1, 2**256 - 1]
    limbs = L.ints_to_limbs16(vals)
    if int(limbs.max()) > L.LIMB_MASK:
        bad("ints-mask", f"ints_to_limbs16 emits limb {int(limbs.max()):#x} "
            f"> declared {L.LIMB_BITS}-bit mask")
    if L.limbs16_to_ints(limbs) != [v % (2**256) for v in vals]:
        bad("ints-roundtrip", "ints<->limbs16 round-trip diverges on "
            "extreme values")
    return out


def lint_matmul_cap() -> list:
    """PROVE the DFT-matmul exactness budget at the shipped
    `ntt._MATMUL_MAX_LOGN` — closed-form over exact host integers, so the cap
    is a theorem, not an assertion. The traced `ntt.dft_matmul*` specs walk
    the real jaxpr structure at a lintable length; this check scales the same
    bounds to the cap, where materializing the [n, n·32] table (512 MB at
    n=4096) is not lintable. Any cap bump without re-widening the group
    split / REDC radix lands here as a KL-OVERFLOW error."""
    from ..ops import field_mxu as MX
    from ..ops import field_ops as F
    from ..ops import ntt as NTT

    out = []
    file = "spectre_tpu/ops/ntt.py"
    int32 = (1 << 31) - 1

    def bad(detail, msg):
        out.append(Finding("kernel", "KL-OVERFLOW", Severity.ERROR, file,
                           "ntt.matmul_cap", msg,
                           key=f"KL-OVERFLOW:ntt.matmul_cap:{detail}"))

    logn = NTT._MATMUL_MAX_LOGN
    n = 1 << logn
    p = F.fr_ctx().p
    width = NTT._conv_group_width(logn)

    # (1) first dot_general column: x8 lanes ≤ 255 times the twiddle-limb
    # matrix's worst contraction column |sum| ≤ 255·n (entries are 8-bit)
    if 255 * 255 * n > int32:
        bad("dot-g", f"point-axis dot_general column 255²·n = {255*255*n} "
            f"exceeds int32 at n={n}")
    # (2) grouped one-hot collapse + carry scan: the REAL conv matrix's
    # per-group column count times the per-product bound, plus the running
    # carry (≤ peak/255) — peak W·n·255·256
    s = MX.conv_matrix(MX.L8, MX.L8, 63)
    for lo in range(0, MX.L8, width):
        colsum = int(np.abs(s[lo * MX.L8:(lo + width) * MX.L8]
                            .astype(np.int64)).sum(axis=0).max())
        peak = colsum * n * 255 * 256       # column sum + carry-scan remainder
        if peak > int32:
            bad("conv-col", f"grouped collapse column at i1∈[{lo},{lo+width})"
                f": colsum {colsum} · n·255·256 = {peak} exceeds int32 at "
                f"the shipped cap n={n} (widen the split: _conv_group_width)")
    # (3) group-sum renormalization: ≤ ceil(32/W) exact 8-bit lanes per limb
    groups = (MX.L8 + width - 1) // width
    if groups * 255 + groups > int32:       # trivially true; kept explicit
        bad("group-sum", "group-sum lanes exceed int32")
    # (4) t and m·p fit the declared limb count
    if n * p * p >= 1 << (8 * NTT._T_LIMBS):
        bad("t-limbs", f"t < n·p² needs more than _T_LIMBS={NTT._T_LIMBS} "
            f"8-bit limbs at n={n}")
    if (1 << NTT._REDC_SHIFT) * p >= 1 << (8 * NTT._T_LIMBS):
        bad("mp-limbs", f"m·p < 2^{NTT._REDC_SHIFT}·p overflows "
            f"_T_LIMBS={NTT._T_LIMBS} limbs")
    # (5) single-REDC full reduction: u < n·p²/2^shift + p < 2p needs
    # n·p < 2^shift — the one conditional subtract is only sound under it
    if n * p >= 1 << NTT._REDC_SHIFT:
        bad("redc", f"single-REDC bound n·p < 2^{NTT._REDC_SHIFT} fails at "
            f"n={n}: u < 2p no longer holds (raise _REDC_SHIFT)")
    # (6) REDC limb products: mul_columns columns ≤ limbs·255²
    if NTT._REDC_LIMBS * 255 * 255 > int32:
        bad("mul-cols", "REDC mul_columns column exceeds int32")
    return out


def lint_kernel(spec: KernelSpec) -> list:
    fn, args = spec.build()
    return lint_fn(fn, args, name=spec.name, file=spec.file,
                   in_bits=spec.in_bits, wrap_ok=spec.wrap_ok)


def lint_all_kernels(names=None) -> list:
    findings = []
    for spec in KERNELS:
        if names and spec.name not in names:
            continue
        findings += lint_kernel(spec)
    if not names or "limbs.host" in names:
        findings += lint_limbs_host()
    if not names or "ntt.matmul_cap" in names:
        findings += lint_matmul_cap()
    return findings
