"""Precision-flow lint — dtype/numerics dataflow over the compiled jaxprs.

The trace linter (``analysis.trace_lint``) finds structural TPU hazards
(f64 leaks, captured weights); this pass reasons about PRECISION: where a
low-precision value accumulates, escapes into master state, or walks into
an op whose domain it cannot survive.  It is the static gate that makes
aggressive low-precision work (ROADMAP item 2: quantized collectives,
bf16 master-weight training, int8 weight-only serving; EQuARX,
arXiv:2506.17615) cheap — a bad precision config is a lint finding, not a
burned convergence run.  Like ``trace_lint`` it sees the whole compiled
step as one static dataflow graph, recursing scan/cond/jit sub-jaxprs.

Rules (``N###``):

  N401 low-precision-accumulation   dot/conv/reduce/scan-carry
                                    accumulating in bf16/f16 without an
                                    f32 accumulator
                                    (``preferred_element_type``)
  N402 master-precision-escape      a params/opt-state output leaf of the
                                    train step is produced below master
                                    precision, or its update math ran in
                                    a sub-f32 dtype outside the
                                    sanctioned forward-cast site
  N403 unguarded-domain-hazard      exp/log/rsqrt/div whose input is not
                                    range-guarded by the masked-softmax
                                    max-subtraction (ops/rnn.py
                                    ``_att_softmax`` is the positive
                                    pattern) or an epsilon idiom
  N404 sentinel-literal-overflow    a finite mask/fill literal (the
                                    ``-1e9`` idiom) cast to a dtype whose
                                    finite range it exceeds — under f16
                                    it lands as ±inf and poisons softmax
  N405 low-precision-psum           a cross-replica psum at sub-f32 dtype
                                    with no block-scale structure (no f32
                                    scale psum beside it) — the static
                                    gate a quantized allreduce must pass
  N406 dtype-roundtrip-churn        convert chains f32→bf16→f32: HBM
                                    bandwidth spent quantizing a value
                                    that is immediately promoted back

Allowlist pragma (shared grammar, analysis.pragmas), anchored on the
source line that ISSUES the primitive (``eqn.source_info``)::

    alpha = jnp.exp(score)  # num: allow[N403] scores are clipped upstream

``certify_precision_plan(topology, plan)`` statically verifies a proposed
compute-dtype/master-dtype split over the real ``make_train_step`` body
and renders a per-layer precision certificate — the documented gate for
ROADMAP item 2's quantized/low-precision configs.

Run via ``paddle-tpu lint --numerics [--config ... --compute-dtype ...]``
(``make lint``: package probes + the shipped demo corpus at f32 must be
zero-diagnostic; the bf16 flagship leg is triaged to zero via fixes or
justified pragmas).
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from paddle_tpu.analysis import pragmas as _pragmas
from paddle_tpu.analysis.diagnostics import Diagnostic, Severity

__all__ = [
    "PrecisionCertificate",
    "certify_precision_plan",
    "lint_numerics_jaxpr",
    "lint_numerics_step",
    "lint_numerics_config",
    "lint_numerics_package",
]

# sub-f32 floating dtypes ("low precision" throughout)
_LOW_FLOATS = {"float16", "bfloat16", "float8_e4m3fn", "float8_e5m2"}
# reductions under this extent are numerically safe even in bf16 (the
# partial-sum count is too small to lose mantissa); dot contractions and
# long reduces above it need an f32 accumulator
ACCUM_EXTENT_THRESHOLD = 32

# call-like primitives we inline (operand substitution keeps constants
# and guard facts flowing through — jnp.where wraps its fill literal in a
# nested jit, and the -1e9-under-f16 check (N404) must see through it)
_INLINE_PRIMS = frozenset({
    "jit", "closed_call", "call", "remat2",
    "custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr",
})
# ops a guard/constant fact flows through unchanged
_TRANSPARENT = frozenset({
    "convert_element_type", "broadcast_in_dim", "reshape", "transpose",
    "stop_gradient", "slice", "squeeze", "expand_dims", "copy",
    "reduce_precision", "sharding_constraint", "device_put",
})
# ops with intrinsically bounded outputs (exp of them cannot overflow)
_BOUNDED_PRIMS = frozenset({
    "logistic", "tanh", "erf", "sin", "cos", "sign", "clamp",
})
# ops with non-negative outputs (log/div/rsqrt of them + eps is safe)
_POSITIVE_PRIMS = frozenset({"exp", "abs", "square", "logistic"})

_LAYER_RE = re.compile(r"([A-Za-z_][\w.]*):([\w./@-]+)")


def _is_low(dtype) -> bool:
    return dtype is not None and str(dtype) in _LOW_FLOATS


def _is_float(dtype) -> bool:
    # jnp.issubdtype, not np: the ml_dtypes floats (bfloat16, f8) are not
    # numpy.floating subtypes and np would call every bf16 "not float"
    import jax.numpy as jnp

    try:
        return dtype is not None and jnp.issubdtype(
            np.dtype(dtype), jnp.floating
        )
    except TypeError:
        return False


def _finfo(dtype):
    import jax.numpy as jnp

    return jnp.finfo(np.dtype(dtype))  # ml_dtypes-aware (np.finfo is not)


def _aval_dtype(x):
    aval = getattr(x, "aval", None)
    return getattr(aval, "dtype", None)


# ---------------------------------------------------------------------------
# abstract values + region walk
# ---------------------------------------------------------------------------


class _Val:
    """One dataflow value: producing primitive, input links, optionally a
    statically-known scalar constant."""

    __slots__ = ("kind", "prim", "eqn", "ins", "const", "dtype", "tag")

    def __init__(self, kind, dtype, prim="", eqn=None, ins=(), const=None,
                 tag=""):
        self.kind = kind          # "input" | "const" | "op" | "opaque"
        self.dtype = dtype
        self.prim = prim
        self.eqn = eqn
        self.ins = tuple(ins)
        self.const = const        # known scalar float, else None
        self.tag = tag            # input label (arg path) when known


@dataclasses.dataclass
class _Visit:
    """One analyzed eqn occurrence with resolved operand values."""

    eqn: Any
    invals: Tuple[_Val, ...]
    outvals: Tuple[_Val, ...]
    region: str    # "" top level; "scan", "scan/cond", ... for bodies


def _scalar_const(v) -> Optional[float]:
    try:
        arr = np.asarray(v)
        if arr.size != 1:
            return None
        # via float64, not .kind: ml_dtypes scalars (bfloat16/f8) carry
        # numpy kind 'V' and would lose their const-ness otherwise
        return float(np.asarray(arr, dtype=np.float64).reshape(()))
    except Exception:  # noqa: BLE001 — exotic consts just lose const-ness
        return None


def _sub_jaxprs(params: Dict[str, Any]):
    """Every ClosedJaxpr reachable from an eqn's params."""
    from jax.extend.core import Jaxpr

    def walk(v):
        if hasattr(v, "jaxpr") or isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from walk(x)

    for v in params.values():
        yield from walk(v)


class _Walker:
    """Flatten a closed jaxpr into `_Visit`s, inlining call-like eqns with
    operand substitution and descending into scan/while/cond bodies with
    opaque boundary values."""

    def __init__(self) -> None:
        self.visits: List[_Visit] = []
        self.scan_carries: List[Tuple[Any, int, _Val, str]] = []
        # (scan eqn, carry index, carry-out val inside body, region)

    # -- entry ----------------------------------------------------------
    def walk_closed(self, closed, in_vals: Optional[Sequence[_Val]] = None,
                    region: str = "") -> List[_Val]:
        jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        consts = list(getattr(closed, "consts", ()) or ())
        env: Dict[Any, _Val] = {}
        for var, cval in zip(jaxpr.constvars, consts):
            env[var] = _Val("const", _aval_dtype(var) or getattr(cval, "dtype", None),
                            const=_scalar_const(cval))
        if in_vals is None:
            in_vals = [
                _Val("input", _aval_dtype(v), tag=f"arg{i}")
                for i, v in enumerate(jaxpr.invars)
            ]
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        self._eqns(jaxpr, env, region)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env, var) -> _Val:
        from jax.extend.core import Literal

        if isinstance(var, Literal):
            return _Val("const", _aval_dtype(var), const=_scalar_const(var.val))
        got = env.get(var)
        if got is None:
            got = _Val("opaque", _aval_dtype(var))
        return got

    def _eqns(self, jaxpr, env, region) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            invals = tuple(self._read(env, v) for v in eqn.invars)
            outvals = self._eqn(eqn, prim, invals, region)
            for var, val in zip(eqn.outvars, outvals):
                env[var] = val

    def _eqn(self, eqn, prim, invals, region) -> Tuple[_Val, ...]:
        if prim in _INLINE_PRIMS:
            subs = [s for s in _sub_jaxprs(eqn.params)]
            for sub in subs:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                if len(inner.invars) == len(invals) and len(
                    inner.outvars
                ) == len(eqn.outvars):
                    return tuple(self.walk_closed(sub, invals, region))
            # arity mismatch (hidden consts): analyze bodies opaquely so
            # in-body hazards still fire, outputs stay opaque
            for sub in subs:
                self.walk_closed(sub, None, region or prim)
            return tuple(_Val("opaque", _aval_dtype(v)) for v in eqn.outvars)

        if prim == "scan":
            self._scan(eqn, invals, region)
        elif prim == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    self.walk_closed(sub, None, _join(region, "while"))
        elif prim == "shard_map":
            # descend into the per-shard program: the quantized-allreduce
            # psums (trainer/step.py's quantized path) live here, and N405
            # must see the payload psum AND its f32 scale psum in the SAME
            # region to accept the pair
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                ops = invals if len(inner.invars) == len(invals) else None
                self.walk_closed(sub, ops, _join(region, "shard_map"))
        elif prim == "cond":
            for sub in eqn.params.get("branches", ()):
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                ops = invals[1:]
                if len(inner.invars) == len(ops):
                    self.walk_closed(sub, ops, _join(region, "cond"))
                else:
                    self.walk_closed(sub, None, _join(region, "cond"))

        out = tuple(
            _Val("op", _aval_dtype(v), prim=prim, eqn=eqn, ins=invals,
                 const=self._const_out(prim, eqn, invals, v))
            for v in eqn.outvars
        )
        self.visits.append(_Visit(eqn=eqn, invals=invals, outvals=out,
                                  region=region))
        return out

    def _const_out(self, prim, eqn, invals, outvar) -> Optional[float]:
        """Propagate known scalar constants through shape-transparent ops
        and converts — the -1e9 literal must still be known when the
        convert to f16 happens inside the inlined `_where` jit."""
        if prim in _TRANSPARENT and invals and invals[0].const is not None:
            return invals[0].const
        if prim == "neg" and invals and invals[0].const is not None:
            return -invals[0].const
        return None

    def _scan(self, eqn, invals, region) -> None:
        params = eqn.params
        sub = params.get("jaxpr")
        if sub is None:
            return
        inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        n_consts = int(params.get("num_consts", 0))
        n_carry = int(params.get("num_carry", 0))
        in_vals: List[_Val] = []
        for i, var in enumerate(inner.invars):
            if i < n_consts and i < len(invals):
                in_vals.append(invals[i])
            else:
                in_vals.append(_Val("opaque", _aval_dtype(var)))
        outs = self.walk_closed(sub, in_vals, _join(region, "scan"))
        carry_ins = in_vals[n_consts:n_consts + n_carry]
        carry_outs = outs[:n_carry]
        for i, (cin, cout) in enumerate(zip(carry_ins, carry_outs)):
            if _is_low(cout.dtype) and _accumulates(cout, cin):
                self.scan_carries.append((eqn, i, cout, region))


def _join(region: str, part: str) -> str:
    return f"{region}/{part}" if region else part


def _accumulates(out: _Val, carry_in: _Val, depth: int = 0) -> bool:
    """True when a scan carry output is an add-chain over its own carry
    input — a running accumulator (the numerically lossy pattern in low
    precision), as opposed to a recurrent state that is overwritten."""
    if depth > 6:
        return False
    if out is carry_in:
        return False
    if out.kind != "op":
        return False
    if out.prim in ("add", "add_any"):
        for op in out.ins:
            if op is carry_in:
                return True
            if op.kind == "op" and op.prim in _TRANSPARENT and op.ins and (
                op.ins[0] is carry_in
            ):
                return True
        return any(_accumulates(op, carry_in, depth + 1) for op in out.ins)
    if out.prim in _TRANSPARENT and out.ins:
        return _accumulates(out.ins[0], carry_in, depth + 1)
    return False


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------


def _eqn_site(eqn) -> Tuple[Optional[str], Optional[int]]:
    """(file, line) of the user code that issued this primitive — the
    anchor the ``# num:`` allowlist pragma attaches to."""
    try:
        from jax._src import source_info_util as siu

        frame = siu.user_frame(eqn.source_info.traceback)
        if frame is None:
            return None, None
        return frame.file_name, int(frame.start_line)
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return None, None


def _eqn_layer(eqn) -> Optional[str]:
    """Layer provenance from the jax.named_scope stack the apply loop
    pushes per layer (``type:name`` — the T100 note plane's vocabulary);
    survives jvp()/transpose() decoration on backward-pass eqns."""
    try:
        ns = str(eqn.source_info.name_stack)
    except Exception:  # noqa: BLE001
        return None
    m = _LAYER_RE.search(ns)
    if m:
        return m.group(2)
    return None


def _relpath(path: Optional[str]) -> Optional[str]:
    if not path:
        return path
    marker = "paddle_tpu" + os.sep
    idx = path.rfind(marker)
    if idx >= 0:
        return path[idx:]
    return path


# ---------------------------------------------------------------------------
# guard analysis (N403)
# ---------------------------------------------------------------------------


def _bounded_above(val: _Val, depth: int = 0) -> bool:
    """Conservatively true when ``val`` cannot grow without bound upward —
    exp of it cannot overflow.  The masked-softmax idiom (subtract the
    stop-gradiented running max; ops/rnn.py:_att_softmax) is the canonical
    positive pattern."""
    if depth > 12:
        return False
    if val.const is not None:
        return bool(np.isfinite(val.const))
    if val.kind != "op":
        return False
    p = val.prim
    if p in _BOUNDED_PRIMS:
        return True
    if p in _TRANSPARENT or p in ("reduce_max", "reduce_min", "max", "min"):
        # min/max against a bounded operand bounds the result
        if p in ("max", "min"):
            return any(_bounded_above(x, depth + 1) for x in val.ins)
        return bool(val.ins) and _bounded_above(val.ins[0], depth + 1)
    if p == "sub":
        # x - max(x): the softmax max-subtraction — subtracting a value
        # derived from a running max of the SAME tensor bounds above at 0.
        # Statically we accept: subtrahend chain contains a reduce_max.
        return len(val.ins) == 2 and _contains_prim(
            val.ins[1], "reduce_max", depth + 1
        )
    if p == "neg":
        return bool(val.ins) and _non_negative(val.ins[0], depth + 1)
    if p in ("mul",):
        # scaling by a finite constant preserves boundedness
        return any(x.const is not None and np.isfinite(x.const)
                   for x in val.ins) and any(
            _bounded_above(x, depth + 1) for x in val.ins
        )
    if p == "add":
        return all(_bounded_above(x, depth + 1) for x in val.ins)
    return False


def _contains_prim(val: _Val, prim: str, depth: int = 0) -> bool:
    if depth > 12 or val.kind != "op":
        return False
    if val.prim == prim:
        return True
    if val.prim in _TRANSPARENT or val.prim in ("max", "min", "mul", "add",
                                                "sub", "select_n"):
        return any(_contains_prim(x, prim, depth + 1) for x in val.ins)
    return False


def _non_negative(val: _Val, depth: int = 0) -> bool:
    if depth > 12:
        return False
    if val.const is not None:
        return val.const >= 0.0
    if val.kind != "op":
        return False
    p = val.prim
    if p in _POSITIVE_PRIMS:
        return True
    if p in _TRANSPARENT:
        return bool(val.ins) and _non_negative(val.ins[0], depth + 1)
    if p in ("reduce_sum", "reduce_max", "reduce_min", "cumsum"):
        return bool(val.ins) and _non_negative(val.ins[0], depth + 1)
    if p in ("add", "mul", "max", "min", "div"):
        if p == "max":
            return any(_non_negative(x, depth + 1) for x in val.ins)
        return all(_non_negative(x, depth + 1) for x in val.ins)
    if p == "integer_pow" and int(val.eqn.params.get("y", 0)) % 2 == 0:
        return True
    if p == "sqrt":
        return True
    return False


def _is_tie_count(val: _Val, depth: int = 0) -> bool:
    """``convert(eq(x, broadcast(reduce_max(x))))`` — the membership mask
    the max/min gradient divides its tie count by; at least one element
    equals its own running max, so the summed count is >= 1."""
    if depth > 12 or val.kind != "op":
        return False
    if val.prim in _TRANSPARENT:
        return bool(val.ins) and _is_tie_count(val.ins[0], depth + 1)
    if val.prim in ("eq", "ge", "le"):
        return any(
            _contains_prim(x, "reduce_max", depth + 1)
            or _contains_prim(x, "reduce_min", depth + 1)
            for x in val.ins
        )
    return False


def _nonzero_rescale_of(val: _Val, t: _Val, depth: int = 0) -> bool:
    """True when ``val`` is ``t`` itself scaled only by finite nonzero
    constants (through shape-transparent ops) — nonzero whenever ``t``
    is, which the zero-switch ``where(t == 0, c, val)`` guarantees on the
    branch that selects it."""
    if depth > 8:
        return False
    if val is t:
        return True
    if val.kind != "op":
        return False
    if val.prim in _TRANSPARENT:
        return bool(val.ins) and _nonzero_rescale_of(val.ins[0], t, depth + 1)
    if val.prim in ("mul", "div"):
        hit = False
        for x in val.ins:
            if _nonzero_rescale_of(x, t, depth + 1):
                hit = True
            elif not (
                x.const is not None and np.isfinite(x.const) and x.const != 0.0
            ):
                return False
        return hit
    return False


def _positive_guarded(val: _Val, depth: int = 0) -> bool:
    """True when ``val`` is bounded away from zero from below — an
    epsilon idiom (`x + 1e-6`, `max(x, eps)`), a nonzero constant, or a
    softmax denominator (sum of exp where the max-subtraction pins one
    term at exp(0)=1)."""
    if depth > 12:
        return False
    if val.const is not None:
        return np.isfinite(val.const) and val.const != 0.0
    if val.kind != "op":
        return False
    p = val.prim
    if p in _TRANSPARENT:
        return bool(val.ins) and _positive_guarded(val.ins[0], depth + 1)
    if p == "add":
        # x + eps with eps a positive constant (the documented epsilon
        # idiom — accepted without proving x >= 0, like Adam's
        # sqrt(v)+eps), or a sum of guarded terms
        if any(x.const is not None and x.const > 0.0 for x in val.ins):
            return True
        return all(_positive_guarded(x, depth + 1) for x in val.ins)
    if p == "max":
        return any(
            (x.const is not None and x.const > 0.0)
            or _positive_guarded(x, depth + 1)
            for x in val.ins
        )
    if p == "exp":
        # exp(x - max(x)): at least one term is exp(0) = 1 — and any exp
        # whose argument is max-subtracted cannot be all-zero
        return bool(val.ins) and _contains_prim(val.ins[0], "reduce_max",
                                                depth + 1)
    if p == "select_n":
        # every selectable branch guarded (jax.nn.softmax's backward
        # divides by select(all_masked, 1, 2) — both branches constants)
        if len(val.ins) > 1 and all(
            _positive_guarded(x, depth + 1) for x in val.ins[1:]
        ):
            return True
        # the zero-switch idiom `where(t == 0, c, t*s)` (ops.quantize's
        # block-scale guard): the branch reached when t != 0 is a pure
        # nonzero rescaling of t, so the select output never lands at zero
        pred = val.ins[0] if val.ins else None
        if (
            len(val.ins) == 3 and pred is not None and pred.kind == "op"
            and pred.prim == "eq" and pred.ins
        ):
            t = next((x for x in pred.ins if x.const is None), None)
            against_zero = any(
                x.const == 0.0 for x in pred.ins if x.const is not None
            )
            if (
                t is not None and against_zero
                and _positive_guarded(val.ins[2], depth + 1)
                and _nonzero_rescale_of(val.ins[1], t)
            ):
                return True
        return False
    if p in ("reduce_sum", "cumsum"):
        if bool(val.ins) and _is_tie_count(val.ins[0], depth + 1):
            # sum of eq(x, max(x)) — the max-gradient tie count: the max
            # itself always matches, so the count is >= 1
            return True
        return bool(val.ins) and _positive_guarded(val.ins[0], depth + 1)
    if p in ("mul", "div"):
        return all(_positive_guarded(x, depth + 1) for x in val.ins)
    if p == "sqrt" or p == "rsqrt":
        return bool(val.ins) and _positive_guarded(val.ins[0], depth + 1)
    return False


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------


def _dot_contraction_extent(eqn) -> int:
    try:
        (lhs_c, _rhs_c), _ = eqn.params["dimension_numbers"]
        shape = eqn.invars[0].aval.shape
        ext = 1
        for d in lhs_c:
            ext *= int(shape[d])
        return ext
    except Exception:  # noqa: BLE001
        return ACCUM_EXTENT_THRESHOLD


def _reduce_extent(eqn) -> int:
    try:
        axes = eqn.params.get("axes")
        if axes is None:  # cumsum spells its one axis `axis`
            axes = (eqn.params["axis"],)
        shape = eqn.invars[0].aval.shape
        ext = 1
        for d in axes:
            ext *= int(shape[d])
        return ext
    except Exception:  # noqa: BLE001
        return ACCUM_EXTENT_THRESHOLD


def _diag(rule, severity, message, eqn, hint=None) -> Diagnostic:
    path, line = _eqn_site(eqn)
    return Diagnostic(
        rule=rule, severity=severity, message=message,
        layer=_eqn_layer(eqn), source=_relpath(path), line=line, hint=hint,
    )


def _rule_n401(visits, scan_carries, diags) -> None:
    for v in visits:
        prim = v.eqn.primitive.name
        if prim in ("dot_general", "conv_general_dilated"):
            opdt = [x.dtype for x in v.invals[:2]]
            if not all(_is_low(d) for d in opdt):
                continue
            pet = v.eqn.params.get("preferred_element_type")
            if pet is not None and not _is_low(np.dtype(pet)):
                continue
            if prim == "dot_general" and _dot_contraction_extent(
                v.eqn
            ) < ACCUM_EXTENT_THRESHOLD:
                continue
            diags.append(_diag(
                "N401", Severity.ERROR,
                f"{prim} accumulates in {opdt[0]} (contraction extent "
                f"{_dot_contraction_extent(v.eqn) if prim == 'dot_general' else '?'})"
                " — partial sums truncate every step",
                v.eqn,
                hint="pass preferred_element_type=jnp.float32 (accumulate "
                "in f32, cast the result) — the MXU gives f32 "
                "accumulation for free",
            ))
        elif prim in ("reduce_sum", "cumsum"):
            x = v.invals[0] if v.invals else None
            if x is None or not _is_low(x.dtype):
                continue
            if not _is_low(v.outvals[0].dtype):
                continue  # already accumulating upward
            if _reduce_extent(v.eqn) < ACCUM_EXTENT_THRESHOLD:
                continue
            diags.append(_diag(
                "N401", Severity.ERROR,
                f"{prim} over {_reduce_extent(v.eqn)} elements in "
                f"{x.dtype} — a long low-precision reduction loses "
                "mantissa with every partial",
                v.eqn,
                hint="reduce in f32: x.astype(jnp.float32).sum(...) and "
                "cast back (jax.nn.softmax's own sum does exactly this)",
            ))
    for eqn, idx, cout, _region in scan_carries:
        diags.append(_diag(
            "N401", Severity.ERROR,
            f"scan carry {idx} accumulates (add-chain over its own "
            f"previous value) in {cout.dtype} — the running sum "
            "quantizes every step",
            eqn,
            hint="carry the accumulator in f32 (cast at the scan "
            "boundary); recurrent STATE that is overwritten each step "
            "may stay low-precision",
        ))


def _rule_n402(out_vals, out_labels, master_dtype, diags) -> None:
    master = np.dtype(master_dtype)
    for val, label in zip(out_vals, out_labels):
        if not _is_float(val.dtype):
            continue
        if np.dtype(val.dtype) != master:
            eqn = val.eqn if val.kind == "op" else None
            d = Diagnostic(
                rule="N402", severity=Severity.ERROR,
                message=f"master-state leaf {label} leaves the train step "
                f"at {val.dtype}, not master {master} — repeated updates "
                "at low precision stall convergence (the update quantizes "
                "before it lands)",
                hint="keep params/opt-state at the master dtype; cast to "
                "the compute dtype only on the forward read (the "
                "layer-boundary cast site, core/compiler.py "
                "resolve_layer_call)",
            )
            if eqn is not None:
                path, line = _eqn_site(eqn)
                d = dataclasses.replace(
                    d, layer=_eqn_layer(eqn), source=_relpath(path), line=line
                )
            diags.append(d)
            continue
        low_src = _lowprec_update_source(val)
        if low_src is not None:
            diags.append(_diag(
                "N402", Severity.ERROR,
                f"master-state leaf {label} is produced by upcasting a "
                f"{low_src.dtype} value — the update math itself ran "
                "below master precision (outside the sanctioned "
                "forward-cast site)",
                low_src.eqn if low_src.eqn is not None else val.eqn,
                hint="compute the optimizer update on the f32 master "
                "values; only the forward pass reads the compute-dtype "
                "cast",
            ))


def _lowprec_update_source(val: _Val, depth: int = 0) -> Optional[_Val]:
    """The sub-f32 value a master-state output was upcast from, if its
    producing chain ends in convert(low→master).  Walks through the
    sentinel's per-leaf select (healthy ? new : old) and tuple-ish
    transparents only — anything else is the legitimate f32 math path."""
    if depth > 6 or val.kind != "op":
        return None
    if val.prim == "convert_element_type":
        src = val.ins[0] if val.ins else None
        if src is not None and _is_low(src.dtype) and src.kind == "op":
            return src
        return None
    if val.prim == "select_n":
        for cand in val.ins[1:]:
            hit = _lowprec_update_source(cand, depth + 1)
            if hit is not None:
                return hit
    return None


def _rule_n403(visits, diags) -> None:
    for v in visits:
        prim = v.eqn.primitive.name
        if prim == "exp":
            x = v.invals[0]
            if not _is_float(x.dtype):
                continue
            if _bounded_above(x):
                continue
            diags.append(_diag(
                "N403", Severity.WARNING,
                f"exp of an unguarded {x.dtype} value — overflows to inf "
                "once the argument drifts past the dtype's exp ceiling "
                "(~88 at f32/bf16, ~11 at f16)",
                v.eqn,
                hint="subtract the running max first (the masked-softmax "
                "idiom, ops/rnn.py:_att_softmax) or clamp the argument",
            ))
        elif prim in ("log", "log1p"):
            if prim == "log1p":
                continue  # log1p(0) = 0: safe by construction
            x = v.invals[0]
            if not _is_float(x.dtype):
                continue
            if _positive_guarded(x):
                continue
            diags.append(_diag(
                "N403", Severity.WARNING,
                f"log of an unguarded {x.dtype} value — -inf at zero, "
                "nan below it",
                v.eqn,
                hint="add an epsilon (jnp.log(x + 1e-6)) or route through "
                "the fused log-softmax path (cost layers already do)",
            ))
        elif prim == "rsqrt":
            x = v.invals[0]
            if not _is_float(x.dtype):
                continue
            if _positive_guarded(x):
                continue
            diags.append(_diag(
                "N403", Severity.WARNING,
                f"rsqrt of an unguarded {x.dtype} value — inf at zero",
                v.eqn,
                hint="rsqrt(x + eps), the Adam/LayerNorm epsilon idiom",
            ))
        elif prim == "div":
            if len(v.invals) < 2:
                continue
            den = v.invals[1]
            if not _is_float(den.dtype):
                continue
            if _positive_guarded(den):
                continue
            diags.append(_diag(
                "N403", Severity.WARNING,
                f"division by an unguarded {den.dtype} value — inf/nan "
                "the moment the denominator underflows to zero",
                v.eqn,
                hint="guard the denominator: jnp.maximum(d, eps) or "
                "d + eps (ops/rnn.py:_att_softmax's masked mean is the "
                "positive pattern)",
            ))


def _rule_n404(visits, diags) -> None:
    for v in visits:
        if v.eqn.primitive.name != "convert_element_type":
            continue
        x = v.invals[0] if v.invals else None
        out = v.outvals[0]
        if x is None or x.const is None or not np.isfinite(x.const):
            continue
        if not _is_low(out.dtype):
            continue
        try:
            fmax = float(_finfo(out.dtype).max)
        except ValueError:
            continue
        if abs(x.const) > fmax:
            diags.append(_diag(
                "N404", Severity.ERROR,
                f"sentinel literal {x.const:g} overflows {out.dtype} "
                f"(finite max {fmax:g}) — the mask fill lands as ±inf and "
                "a fully-masked row softmaxes to nan",
                v.eqn,
                hint="derive the fill from the tensor dtype: "
                "jnp.asarray(jnp.finfo(x.dtype).min, x.dtype) or use the "
                "dtype-aware mask helper",
            ))


def _rule_n405(visits, diags) -> None:
    by_region: Dict[str, List[_Visit]] = {}
    for v in visits:
        if v.eqn.primitive.name == "psum":
            by_region.setdefault(v.region, []).append(v)
    for _region, group in by_region.items():
        has_f32 = any(
            any(str(x.dtype) == "float32" for x in v.invals) for v in group
        )
        for v in group:
            for x in v.invals:
                if not (_is_low(x.dtype) or str(x.dtype) == "int8"):
                    continue
                if has_f32:
                    continue  # block-scale structure: scales ride at f32
                diags.append(_diag(
                    "N405", Severity.ERROR,
                    f"cross-replica psum at {x.dtype} with no f32 scale "
                    "psum beside it — quantized gradients allreduce "
                    "without block-scale structure and the reduction "
                    "saturates/biases",
                    v.eqn,
                    hint="block-scale the quantized allreduce (EQuARX, "
                    "arXiv:2506.17615): psum int8/bf16 blocks AND their "
                    "f32 scales, dequantize after — "
                    "ops.quantize.quantized_psum emits the accepted pair "
                    "(quantize_block_scaled/dequantize_block_scaled are "
                    "the building blocks; trainer/step.py's "
                    "quantized_allreduce path uses them)",
                ))


def _rule_n406(visits, diags) -> None:
    for v in visits:
        if v.eqn.primitive.name != "convert_element_type":
            continue
        x = v.invals[0] if v.invals else None
        out = v.outvals[0]
        if x is None or x.kind != "op" or x.prim != "convert_element_type":
            continue
        origin = x.ins[0] if x.ins else None
        if origin is None:
            continue
        if not (_is_float(origin.dtype) and _is_float(x.dtype)
                and _is_float(out.dtype)):
            continue
        if np.dtype(origin.dtype) != np.dtype(out.dtype):
            continue
        try:
            mid_bits = _finfo(x.dtype).nmant
            end_bits = _finfo(out.dtype).nmant
        except ValueError:
            continue
        if mid_bits >= end_bits:
            continue
        diags.append(_diag(
            "N406", Severity.WARNING,
            f"dtype round-trip {origin.dtype}→{x.dtype}→{out.dtype}: "
            "the value is quantized and immediately promoted back — "
            "bandwidth spent destroying mantissa",
            v.eqn,
            hint="keep the value at one dtype across the boundary (hoist "
            "the cast, or drop the intermediate narrow cast)",
        ))


# ---------------------------------------------------------------------------
# pragma filtering
# ---------------------------------------------------------------------------


class _PragmaFilter:
    """Suppress findings whose issuing source line carries a justified
    ``# num: allow[<rule>]`` pragma; tracks per-file pragma usage so
    stale annotations can report uniformly with the lock plane."""

    def __init__(self) -> None:
        self._tables: Dict[str, Dict[int, _pragmas.Pragma]] = {}
        self._roots: Dict[str, str] = {}
        self.used: Dict[str, Set[int]] = {}
        self.pragma_diags: List[Diagnostic] = []

    def _table(self, relpath: str) -> Dict[int, _pragmas.Pragma]:
        got = self._tables.get(relpath)
        if got is not None:
            return got
        table: Dict[int, _pragmas.Pragma] = {}
        path = self._resolve(relpath)
        if path is not None and os.path.exists(path):
            try:
                with open(path, "r", encoding="utf-8") as f:
                    src = f.read()
                table = _pragmas.collect(src, "num", relpath,
                                         self.pragma_diags)
            except OSError:
                table = {}
        self._tables[relpath] = table
        return table

    def _resolve(self, relpath: str) -> Optional[str]:
        if os.path.isabs(relpath):
            return relpath
        import paddle_tpu

        base = os.path.dirname(os.path.dirname(
            os.path.abspath(paddle_tpu.__file__)
        ))
        return os.path.join(base, relpath)

    def filter(self, diags: List[Diagnostic]) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for d in diags:
            if d.source and d.line:
                pragma = self._table(d.source).get(d.line)
                if pragma is not None and pragma.suppresses(d.rule):
                    self.used.setdefault(d.source, set()).add(d.line)
                    continue
            out.append(d)
        return out

    def stale(self) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for relpath, table in sorted(self._tables.items()):
            out.extend(_pragmas.stale_findings(
                table, self.used.get(relpath, ()), "num", relpath,
            ))
        return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def lint_numerics_jaxpr(
    closed,
    *,
    in_vals: Optional[Sequence[_Val]] = None,
    apply_pragmas: bool = True,
    _filter: Optional[_PragmaFilter] = None,
) -> List[Diagnostic]:
    """All structural N-rules (N401/N403/N404/N405/N406) over one closed
    jaxpr; N402 needs the train-step arg/out mapping — use
    :func:`lint_numerics_step`."""
    walker = _Walker()
    walker.walk_closed(closed, in_vals)
    diags: List[Diagnostic] = []
    _rule_n401(walker.visits, walker.scan_carries, diags)
    _rule_n403(walker.visits, diags)
    _rule_n404(walker.visits, diags)
    _rule_n405(walker.visits, diags)
    _rule_n406(walker.visits, diags)
    if apply_pragmas:
        f = _filter or _PragmaFilter()
        diags = f.filter(diags)
    return diags


def _trace_and_lint(
    fn,
    example_args,
    master_argnums: Sequence[int],
    master_dtype,
) -> Tuple[List[Diagnostic], _Walker]:
    """The ONE trace+rules body behind :func:`lint_numerics_step` and
    :func:`certify_precision_plan` — trace ``fn`` on the example args,
    walk the jaxpr, run every structural rule, and run the N402
    master-precision check over the flattened outputs of the argnums that
    hold master state.  Returns the UNFILTERED diagnostics plus the
    walker (the certificate reads its visits for per-layer rows)."""
    import jax

    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    walker = _Walker()

    in_vals: Optional[List[_Val]] = []
    for argnum, arg in enumerate(example_args):
        for path, leaf in jax.tree_util.tree_leaves_with_path(arg):
            label = f"arg{argnum}{jax.tree_util.keystr(path)}"
            in_vals.append(_Val("input", getattr(leaf, "dtype", None),
                                tag=label))
    if len(in_vals) != len(closed.jaxpr.invars):
        in_vals = None  # structure we can't map: rules still run

    out_vals = walker.walk_closed(closed, in_vals)

    out_labels: List[str] = []
    master_flags: List[bool] = []
    parts = out_shape if isinstance(out_shape, tuple) else (out_shape,)
    for outnum, part in enumerate(parts):
        for path, _leaf in jax.tree_util.tree_leaves_with_path(part):
            out_labels.append(f"out{outnum}{jax.tree_util.keystr(path)}")
            master_flags.append(outnum in master_argnums)

    diags: List[Diagnostic] = []
    _rule_n401(walker.visits, walker.scan_carries, diags)
    _rule_n403(walker.visits, diags)
    _rule_n404(walker.visits, diags)
    _rule_n405(walker.visits, diags)
    _rule_n406(walker.visits, diags)
    if len(out_labels) == len(out_vals):
        masters = [
            (v, lbl) for v, lbl, flag in
            zip(out_vals, out_labels, master_flags) if flag
        ]
        _rule_n402([v for v, _ in masters], [l for _, l in masters],
                   master_dtype, diags)
    return diags, walker


def lint_numerics_step(
    fn,
    *example_args,
    master_argnums: Sequence[int] = (0, 2),
    master_dtype=np.float32,
    apply_pragmas: bool = True,
    _filter: Optional[_PragmaFilter] = None,
) -> List[Diagnostic]:
    """Trace ``fn`` (a train-step body: ``(params, state, opt_state,
    batch, rng) -> (params, state, opt_state, metrics)``) on example args
    and run every N-rule, including the N402 master-precision check over
    the argnums that hold master state."""
    diags, _walker = _trace_and_lint(
        fn, example_args, master_argnums, master_dtype
    )
    if apply_pragmas:
        f = _filter or _PragmaFilter()
        diags = f.filter(diags)
    return diags


# -- probe construction ------------------------------------------------------


_LABEL_CONSUMERS = frozenset({
    "cross_entropy", "softmax_with_cost", "classification_cost",
    "multi_class_cross_entropy", "classification_error", "huber_cost",
    "crf", "crf_decoding", "ctc", "warp_ctc", "nce", "hsigmoid",
})


def _infer_probe_types(topology) -> Dict[str, Any]:
    """Probe-type overrides for v1 configs parsed WITHOUT a data provider:
    their slots sit at the parse-time dense placeholder, but the consumers
    pin what a real feed would be — an embedding input is an id sequence,
    a cost layer's label input is integer ids (sequence-shaped when the
    prediction side is a recurrent_group's per-step output)."""
    from paddle_tpu.core.data_types import (
        integer_value,
        integer_value_sequence,
    )

    data_names = set(topology.data_layers())
    overrides: Dict[str, Any] = {}
    for _name, conf in topology.layers.items():
        ins = list(conf.inputs)
        if conf.type == "embedding" and ins and ins[0] in data_names:
            dim = topology.layers[ins[0]].size
            overrides[ins[0]] = integer_value_sequence(dim)
        elif conf.type in _LABEL_CONSUMERS and len(ins) >= 2 \
                and ins[1] in data_names:
            dim = topology.layers[ins[1]].size
            pred = topology.layers.get(ins[0])
            seqish = pred is not None and pred.type in (
                "recurrent_group", "gru_step", "lstm_step",
            )
            overrides[ins[1]] = (
                integer_value_sequence(dim) if seqish else integer_value(dim)
            )
    return overrides


def _probe_rows(topology, batch_size: int = 4, seq_len: int = 6,
                overrides: Optional[Dict[str, Any]] = None):
    """Synthesize one deterministic feeder batch for a topology from its
    declared data types — the numerics lint needs real shapes/dtypes, not
    real data."""
    from paddle_tpu.core.data_types import SeqLevel, SlotKind

    overrides = overrides or {}
    rows = []
    for r in range(batch_size):
        row = []
        for _name, t in topology.data_types():
            t = overrides.get(_name, t)
            if t.kind == SlotKind.DENSE:
                v = [0.25 + 0.01 * r] * t.dim
            elif t.kind == SlotKind.INDEX:
                v = (r % max(t.dim, 1))
            else:  # sparse slots: a couple of active ids
                v = [0, min(1, t.dim - 1)]
            if t.seq == SeqLevel.SEQ:
                v = [v] * seq_len if t.kind != SlotKind.INDEX else [
                    (r + i) % max(t.dim, 1) for i in range(seq_len)
                ]
            elif t.seq == SeqLevel.SUB_SEQ:
                inner = [v] * 2 if t.kind != SlotKind.INDEX else [
                    r % max(t.dim, 1)
                ] * 2
                v = [inner, inner]
            row.append(v)
        rows.append(tuple(row))
    return rows


def _probe_batch(topology, batch_size: int = 4, seq_len: int = 6,
                 overrides: Optional[Dict[str, Any]] = None):
    from paddle_tpu.reader.feeder import DataFeeder, feed_dtypes_of

    overrides = overrides or {}
    types = [
        (name, overrides.get(name, t)) for name, t in topology.data_types()
    ]
    feeder = DataFeeder(types, feed_dtypes=feed_dtypes_of(topology))
    return feeder(_probe_rows(topology, batch_size, seq_len, overrides))


def _step_parts(topology, optimizer=None, compute_dtype=None,
                master_dtype=None, batch_size: int = 4, seq_len: int = 6,
                infer_types: bool = False):
    """(step_body, example_args) for the REAL train step of a topology at
    the given precision plan — the jaxpr certify/lint run over."""
    import jax

    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.trainer.step import _train_step_body

    if optimizer is None:
        import paddle_tpu.optimizer as O

        optimizer = O.Adam(learning_rate=1e-3)
    kwargs: Dict[str, Any] = {}
    if master_dtype is not None:
        kwargs["dtype"] = np.dtype(master_dtype)
    if compute_dtype is not None:
        kwargs["compute_dtype"] = np.dtype(compute_dtype)
    net = CompiledNetwork(topology, **kwargs)
    overrides = _infer_probe_types(topology) if infer_types else None
    batch = _probe_batch(topology, batch_size, seq_len, overrides)
    params, state = net.init(jax.random.PRNGKey(0))
    if getattr(net, "has_dynamic_widths", False):
        params, chg = net.resolve_dynamic_widths(params, batch)
        del chg
    opt_state = optimizer.init(params)
    step = _train_step_body(net, optimizer, sentinel=True)
    return step, (params, state, opt_state, batch, jax.random.PRNGKey(1))


def lint_numerics_config(
    config_path: str,
    config_args: str = "",
    compute_dtype=None,
    master_dtype=None,
    apply_pragmas: bool = True,
    _filter: Optional[_PragmaFilter] = None,
) -> List[Diagnostic]:
    """Parse a v1 config and precision-lint its REAL train step (the
    parsed settings' optimizer, a synthesized probe batch) at the given
    dtype plan — the ``paddle-tpu lint --numerics --config`` body."""
    from paddle_tpu.v1_compat import make_optimizer, parse_config

    parsed = parse_config(os.path.abspath(config_path), config_args)
    try:
        optimizer = make_optimizer(parsed.settings)
    except Exception:  # noqa: BLE001 — exotic settings: probe with Adam
        optimizer = None
    step, args = _step_parts(
        parsed.topology, optimizer,
        compute_dtype=compute_dtype, master_dtype=master_dtype,
        infer_types=True,
    )
    return lint_numerics_step(
        step, *args,
        master_dtype=np.dtype(master_dtype or np.float32),
        apply_pragmas=apply_pragmas, _filter=_filter,
    )


def lint_numerics_package(
    compute_dtype=None,
    master_dtype=None,
    check_stale_pragmas: Optional[bool] = None,
) -> List[Diagnostic]:
    """The package leg of ``paddle-tpu lint --numerics``: precision-lint
    the shipped step builders over probe topologies that exercise the
    planes the flagships use (dense MLP, LSTM sequence path, the fused
    attention-GRU decoder), plus ``# num:`` pragma hygiene.  Stale-pragma
    reporting defaults to ON for sub-f32 runs (the dtype context the
    pragmas exist for) and OFF at f32."""
    if check_stale_pragmas is None:
        check_stale_pragmas = compute_dtype is not None and _is_low(
            np.dtype(compute_dtype)
        )
    f = _PragmaFilter()
    diags: List[Diagnostic] = []
    for topo in _probe_topologies():
        step, args = _step_parts(
            topo, None, compute_dtype=compute_dtype,
            master_dtype=master_dtype,
        )
        diags.extend(lint_numerics_step(step, *args, _filter=f))
    if check_stale_pragmas:
        # load EVERY package file's pragmas first: the hygiene findings
        # (empty justifications) they append must land in pragma_diags
        # BEFORE it is folded into the result below
        _load_package_pragmas(f)
        diags.extend(f.pragma_diags)
        diags.extend(f.stale())
    else:
        diags.extend(f.pragma_diags)
    return diags


def _probe_topologies():
    """Small topologies covering the numerics-relevant layer planes: the
    MLP (dense dot + softmax CE), the LSTM text path (embedding, scan
    recurrence, pooling), and the attention decoder (masked softmax, the
    fused GRU core)."""
    import paddle_tpu as paddle
    from paddle_tpu.core.topology import Topology, reset_auto_names

    L, A = paddle.layer, paddle.activation
    topos = []

    reset_auto_names()
    x = L.data("x", paddle.data_type.dense_vector(64))
    h = L.fc(x, size=64, act=A.Relu())
    pred = L.fc(h, size=10, act=A.Softmax())
    y = L.data("y", paddle.data_type.integer_value(10))
    topos.append(Topology([L.classification_cost(input=pred, label=y)]))

    reset_auto_names()
    w = L.data("w", paddle.data_type.integer_value_sequence(50))
    emb = L.embedding(w, size=32)
    lstm = paddle.networks.simple_lstm(input=emb, size=32)
    pooled = L.pooling(lstm, pooling_type=paddle.pooling.Max())
    out = L.fc(pooled, size=4, act=A.Softmax())
    lab = L.data("lab", paddle.data_type.integer_value(4))
    topos.append(Topology([L.classification_cost(input=out, label=lab)]))

    reset_auto_names()
    from paddle_tpu.models.seq2seq import seq2seq_cost

    cost, _ = seq2seq_cost(40, 45, word_dim=16, hidden_dim=16)
    topos.append(Topology([cost]))

    # a plain recurrent_group (no fused-core match) so the GENERIC scan
    # path — and its backward's carried weight-cotangent accumulation —
    # is exercised at the probe dtype too
    reset_auto_names()
    w2 = L.data("w2", paddle.data_type.integer_value_sequence(30))
    emb2 = L.embedding(w2, size=16)

    def _step(x):
        prev = L.memory("h", 16)
        return L.fc([x, prev], size=16, act=A.Tanh(), name="h")

    rec = L.recurrent_group(step=_step, input=emb2)
    pooled2 = L.pooling(rec, pooling_type=paddle.pooling.Max())
    out2 = L.fc(pooled2, size=4, act=A.Softmax())
    lab2 = L.data("lab2", paddle.data_type.integer_value(4))
    topos.append(Topology([L.classification_cost(input=out2, label=lab2)]))
    return topos


def _load_package_pragmas(f: _PragmaFilter) -> None:
    """Ensure every package file's ``# num:`` pragmas are in the filter's
    tables so stale reporting covers pragmas in files the probe traces
    never reached."""
    import paddle_tpu

    root = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            f._table(_relpath(path))


# ---------------------------------------------------------------------------
# precision-plan certification
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PrecisionCertificate:
    """The static verdict on one compute/master dtype split: per-layer
    rows plus the N-rule findings the plan would ship with."""

    ok: bool
    compute_dtype: str
    master_dtype: str
    diagnostics: List[Diagnostic]
    rows: List[Dict[str, Any]]  # name, type, dtype, n_dot, acc, hazards

    def format(self) -> str:
        head = (
            f"precision certificate: compute={self.compute_dtype} "
            f"master={self.master_dtype} -> "
            f"{'ACCEPT' if self.ok else 'REJECT'}"
        )
        w = max([16] + [len(r["layer"]) for r in self.rows]) + 1
        lines = [head, f"{'layer':<{w}}{'type':<18}{'compute':<10}"
                 f"{'dots(acc)':<12}{'hazards':<8}"]
        for r in self.rows:
            lines.append(
                f"{r['layer']:<{w}}{r['type']:<18}{r['dtype']:<10}"
                f"{str(r['dots']) + '(' + r['acc'] + ')':<12}"
                f"{r['hazards']:<8}"
            )
        if self.diagnostics:
            from paddle_tpu.analysis.diagnostics import format_diagnostics

            lines.append(format_diagnostics(self.diagnostics))
        return "\n".join(lines)


def certify_precision_plan(
    topology,
    plan: Dict[str, Any],
    optimizer=None,
) -> PrecisionCertificate:
    """Statically verify a precision plan over the REAL train-step jaxpr.

    ``plan``: ``{"compute_dtype": ..., "master_dtype": ...,
    "quantized_weights": bool}`` (names or dtypes; master defaults to
    float32).  ACCEPT iff no ERROR-severity N-rule fires — in particular a
    plan whose master dtype is sub-f32 (params updated in bf16) is
    rejected by N402, while the sanctioned master-f32/compute-bf16 split
    passes on the shipped flagships.  This is the gate a ROADMAP-item-2
    quantized/low-precision config must clear before it is allowed near a
    convergence run.

    ``quantized_weights`` declares weight-ONLY int8 (the serving decode
    bundle as int8 blocks + f32 scales, dequantized in-graph): it leaves
    the traced train plane untouched, so the sanctioned splits still
    ACCEPT.  A NON-FLOAT master or compute dtype (int8 master params /
    optimizer state) is rejected outright, without tracing: integer state
    cannot carry the update accumulation at all."""
    compute = np.dtype(plan.get("compute_dtype") or np.float32)
    master = np.dtype(plan.get("master_dtype") or np.float32)
    for role, dt in (("master", master), ("compute", compute)):
        if not _is_float(dt):
            d = Diagnostic(
                rule="N402", severity=Severity.ERROR,
                message=f"precision plan asks for {role} dtype {dt} — "
                "integer master params/optimizer state cannot accumulate "
                "updates (every step requantizes the whole trajectory); "
                "quantization must stay weight-only",
                hint="keep master/compute dtypes float; declare int8 "
                "serving weights via plan['quantized_weights']=True "
                "(ops.quantize.quantize_weight_bundle)",
            )
            return PrecisionCertificate(
                ok=False, compute_dtype=str(compute),
                master_dtype=str(master), diagnostics=[d], rows=[],
            )

    f = _PragmaFilter()
    step, args = _step_parts(
        topology, optimizer, compute_dtype=compute, master_dtype=master,
        infer_types=True,
    )
    # the SAME trace+rules body the lint runs — the gate can never be
    # weaker than `paddle-tpu lint --numerics` on the same plan
    diags, walker = _trace_and_lint(step, args, (0, 2), master)
    diags = f.filter(diags)
    # malformed (empty-justification) pragmas in the files this trace
    # touched keep the certificate honest: hygiene findings reject too
    diags = diags + f.pragma_diags

    # per-layer rows from the named-scope groups of the traced step
    per_layer: Dict[str, Dict[str, Any]] = {}
    layer_types = {
        name: conf.type for name, conf in topology.layers.items()
    }
    for v in walker.visits:
        layer = _eqn_layer(v.eqn)
        if layer is None or layer not in layer_types:
            continue
        row = per_layer.setdefault(layer, {
            "layer": layer, "type": layer_types[layer],
            "dtype": "-", "dots": 0, "acc": "-", "hazards": 0,
        })
        prim = v.eqn.primitive.name
        if prim in ("dot_general", "conv_general_dilated"):
            row["dots"] += 1
            opdt = v.invals[0].dtype if v.invals else None
            # the LOWEST operand dtype seen is the layer's compute dtype
            # (backward-pass dots at f32 must not mask a bf16 forward)
            if opdt is not None and (row["dtype"] == "-" or _is_low(opdt)):
                row["dtype"] = str(opdt)
                pet = v.eqn.params.get("preferred_element_type")
                row["acc"] = str(np.dtype(pet)) if pet is not None else str(
                    opdt
                )
    hazard_lines = {
        (d.layer, d.rule) for d in diags if d.layer is not None
    }
    for layer, rule in hazard_lines:
        if layer in per_layer:
            per_layer[layer]["hazards"] += 1
    rows = [per_layer[k] for k in topology.order if k in per_layer]

    from paddle_tpu.analysis.diagnostics import errors

    return PrecisionCertificate(
        ok=not errors(diags),
        compute_dtype=str(compute),
        master_dtype=str(master),
        diagnostics=diags,
        rows=rows,
    )
