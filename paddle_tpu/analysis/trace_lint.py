"""Trace-hygiene analyzer — jaxpr-level TPU hazard checks on the compiled
train/eval step.

The graph linter (``analysis.graph_lint``) sees the model *description*;
this pass sees what will actually be handed to XLA.  Because the whole step
is one traced program (core/compiler.py), the jaxpr is a complete static
dataflow graph of the computation — inspecting it is pure host-side
analysis, the ahead-of-time-validation property the TF/Julia-to-TPU papers
exploit (PAPERS.md).

Rules (``T###``):

  T101 f64-leak              float64 values or f64 convert_element_type in
                             the traced program (TPUs emulate f64 at ~1/20
                             throughput; usually a stray Python float with
                             x64 enabled)
  T102 const-captured-array  a large array baked into the jaxpr as a
                             CONSTANT instead of an argument (weights
                             captured by closure: re-shipped per compile,
                             cache-key churn, no donation)
  T103 host-callback         host callbacks / debug prints inside the hot
                             path (each one is a device→host sync)
  T104 off-ladder-shape      an observed batch shape whose padded sequence
                             extents sit off the bucketing ladder — every
                             such batch is its own jit cache entry
  T105 shape-explosion       distinct batch shapes exceed the ladder
                             budget: the step recompiles per batch instead
                             of per rung
  T106 undonated-carry       a large input buffer (params / opt-state /
                             any carried-state leaf) is returned updated
                             but NOT donated — XLA double-buffers it: 2x
                             HBM held and a device copy every step

``trace_step`` builds the jaxpr of a step function exactly as jit would see
it; ``recompile_audit`` replays a reader's observed batch shapes against the
``CompileShapeCache`` contract (core/compiler.py); ``donation_audit`` checks
the train step / epoch program's carried buffers are donated (T106).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from paddle_tpu.analysis.diagnostics import Diagnostic, Severity
from paddle_tpu.core.batch import (
    DEFAULT_LADDER,
    DEFAULT_SUB_LADDER,
    batch_shape_key,
)

# one device→host sync per step each; debug_print compiles to a callback
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback_call",
})

# elements; 64 KiB of f32 — parameters are (much) bigger, batch literals too
DEFAULT_CONST_ELEMS = 16384


def _walk_jaxprs(jaxpr) -> Iterable[Tuple[Any, List]]:
    """Yield (jaxpr, consts) for the closed jaxpr and every sub-jaxpr
    (scan/cond/while bodies, closed calls) it contains."""
    seen = set()

    def visit(j, consts):
        if id(j) in seen:
            return
        seen.add(id(j))
        yield j, consts
        for eqn in j.eqns:
            for v in eqn.params.values():
                for sub in _iter_jaxpr_params(v):
                    if hasattr(sub, "jaxpr"):  # ClosedJaxpr
                        yield from visit(sub.jaxpr, list(sub.consts))
                    else:
                        yield from visit(sub, [])

    closed = jaxpr
    if hasattr(closed, "jaxpr"):
        yield from visit(closed.jaxpr, list(closed.consts))
    else:
        yield from visit(closed, [])


def _iter_jaxpr_params(v):
    from jax.extend.core import Jaxpr

    if hasattr(v, "jaxpr") or isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _iter_jaxpr_params(x)


def _aval_dtype(var) -> Optional[np.dtype]:
    aval = getattr(var, "aval", None)
    dt = getattr(aval, "dtype", None)
    return np.dtype(dt) if dt is not None else None


def lint_jaxpr(
    jaxpr,
    *,
    const_elem_threshold: int = DEFAULT_CONST_ELEMS,
    source: Optional[str] = None,
) -> List[Diagnostic]:
    """Hazard-scan a (closed) jaxpr: T101 f64 leaks, T102 closure-captured
    array constants, T103 host callbacks.  Use ``jax.make_jaxpr(fn)(*args)``
    (or :func:`trace_step`) to obtain the jaxpr of the step exactly as
    ``jax.jit`` would trace it."""
    diags: List[Diagnostic] = []
    f64 = np.dtype(np.float64)
    f64_sites: List[str] = []
    callbacks: List[str] = []
    big_consts: List[str] = []

    for j, consts in _walk_jaxprs(jaxpr):
        for cv, cval in zip(getattr(j, "constvars", ()), consts):
            size = int(np.size(cval)) if hasattr(cval, "shape") else 0
            if size >= const_elem_threshold:
                dt = getattr(cval, "dtype", "?")
                big_consts.append(
                    f"{tuple(np.shape(cval))} {dt} ({size} elems)"
                )
            if _aval_dtype(cv) == f64:
                f64_sites.append(f"constant {tuple(np.shape(cval))}")
        for eqn in j.eqns:
            prim = eqn.primitive.name
            if prim in _CALLBACK_PRIMS:
                callbacks.append(prim)
            if any(_aval_dtype(v) == f64 for v in eqn.outvars):
                if prim == "convert_element_type":
                    f64_sites.append(f"{prim} -> float64")
                else:
                    f64_sites.append(f"{prim} output")

    if f64_sites:
        uniq = sorted(set(f64_sites))
        diags.append(Diagnostic(
            rule="T101", severity=Severity.ERROR, source=source,
            message=f"float64 values in the traced step: {uniq[:6]}"
            + (f" (+{len(uniq) - 6} more)" if len(uniq) > 6 else ""),
            hint="TPUs run f64 at a fraction of f32 throughput; find the "
            "promoting Python float / np.float64 literal, or keep "
            "jax_enable_x64 off for training steps",
        ))
    if big_consts:
        diags.append(Diagnostic(
            rule="T102", severity=Severity.WARNING, source=source,
            message="large arrays are baked into the jaxpr as constants "
            f"instead of arguments: {big_consts[:4]}"
            + (f" (+{len(big_consts) - 4} more)" if len(big_consts) > 4 else ""),
            hint="a closure captured weights/batch data at trace time — "
            "pass them as function arguments so the executable is "
            "shape-polymorphic over them and buffers can be donated",
        ))
    if callbacks:
        counts = {p: callbacks.count(p) for p in sorted(set(callbacks))}
        diags.append(Diagnostic(
            rule="T103", severity=Severity.WARNING, source=source,
            message=f"host callbacks inside the traced step: {counts}",
            hint="each callback is a device->host round-trip per step; "
            "strip debug_print/callback wrappers from the hot path",
        ))
    return diags


def trace_step(fn, *example_args, **example_kwargs):
    """The closed jaxpr of ``fn`` on the example arguments — exactly the
    program jit would compile for these shapes (abstract trace; no FLOPs,
    no device transfer)."""
    return jax.make_jaxpr(fn)(*example_args, **example_kwargs)


def lint_step(
    fn,
    *example_args,
    const_elem_threshold: int = DEFAULT_CONST_ELEMS,
    source: Optional[str] = None,
    **example_kwargs,
) -> List[Diagnostic]:
    """Trace ``fn`` on example args and hazard-scan the result."""
    return lint_jaxpr(
        trace_step(fn, *example_args, **example_kwargs),
        const_elem_threshold=const_elem_threshold,
        source=source,
    )


# ---------------------------------------------------------------------------
# buffer-donation audit (T106)
# ---------------------------------------------------------------------------


def donation_audit(
    fn,
    *example_args,
    donate_argnums: Optional[Sequence[int]] = None,
    carry_elem_threshold: int = DEFAULT_CONST_ELEMS,
    source: Optional[str] = None,
) -> List[Diagnostic]:
    """T106: flag large CARRIED buffers that are copied instead of donated.

    A train step / epoch program returns updated versions of its big
    inputs (params, optimizer slots, carried state).  When such an input
    is not donated, XLA cannot alias it into the matching output: the
    program holds BOTH generations in HBM (2x the carry) and spends a
    copy per dispatch.  The heuristic mirrors what XLA's aliasing pass
    needs: a non-donated input leaf of ``carry_elem_threshold``+ elements
    whose (shape, dtype) also appears among the outputs is a carried
    buffer that will be double-buffered.

    ``fn`` may be a jitted function — its own ``donate_argnums`` are read
    back out of the traced jit equation, so the audit checks what jit
    will actually honor; for a plain function pass ``donate_argnums``
    explicitly (the jit spelling the builder intends)."""
    closed = trace_step(fn, *example_args)
    jaxpr = closed.jaxpr
    leaf_lists = [jax.tree_util.tree_leaves(a) for a in example_args]
    counts = [len(leaves) for leaves in leaf_lists]
    arg_of: List[int] = []
    for argnum, cnt in enumerate(counts):
        arg_of.extend([argnum] * cnt)
    if len(arg_of) != len(jaxpr.invars):
        return []  # kwargs/captured structure we can't map — stay silent

    donated: Optional[List[bool]] = None
    eqns = jaxpr.eqns
    if (
        len(eqns) == 1
        and eqns[0].primitive.name == "jit"
        and "donated_invars" in eqns[0].params
        and list(eqns[0].invars) == list(jaxpr.invars)
    ):
        # a jitted fn traces to one jit eqn; its donated_invars are the
        # flags jit will compile with — the ground truth
        donated = list(eqns[0].params["donated_invars"])
    if donated is None:
        dset = set(donate_argnums or ())
        donated = [argnum in dset for argnum in arg_of]

    out_avals: set = set()
    for v in jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "shape", None) is not None:
            out_avals.add((tuple(aval.shape), str(aval.dtype)))

    per_arg: Dict[int, List[str]] = {}
    per_arg_bytes: Dict[int, int] = {}
    for i, v in enumerate(jaxpr.invars):
        if donated[i]:
            continue
        aval = getattr(v, "aval", None)
        if aval is None or not getattr(aval, "shape", None):
            continue
        size = int(np.prod(aval.shape))
        if size < carry_elem_threshold:
            continue
        sig = (tuple(aval.shape), str(aval.dtype))
        if sig not in out_avals:
            continue  # read-only input (batch data): no copy to save
        per_arg.setdefault(arg_of[i], []).append(
            f"{sig[0]} {sig[1]}"
        )
        per_arg_bytes[arg_of[i]] = per_arg_bytes.get(arg_of[i], 0) + (
            size * np.dtype(aval.dtype).itemsize
        )

    diags: List[Diagnostic] = []
    for argnum in sorted(per_arg):
        shapes = per_arg[argnum]
        mb = per_arg_bytes[argnum] / 1e6
        diags.append(Diagnostic(
            rule="T106", severity=Severity.WARNING, source=source,
            message=(
                f"argument {argnum} carries {len(shapes)} large buffer(s) "
                f"({mb:.1f} MB) returned updated but NOT donated: "
                f"{shapes[:4]}"
                + (f" (+{len(shapes) - 4} more)" if len(shapes) > 4 else "")
            ),
            hint="add donate_argnums for carried state (params/opt-state/"
            "scan carries) so XLA aliases the buffers — an undonated "
            "carry is double-buffered: 2x HBM held and one device copy "
            "per dispatch",
        ))
    return diags


# ---------------------------------------------------------------------------
# recompile-churn audit (T104/T105)
# ---------------------------------------------------------------------------


def recompile_audit(
    observed,
    *,
    ladder: Sequence[int] = DEFAULT_LADDER,
    sub_ladder: Sequence[int] = DEFAULT_SUB_LADDER,
    max_shapes: Optional[int] = None,
    source: Optional[str] = None,
) -> List[Diagnostic]:
    """Replay observed batch shapes against the shape-ladder contract.

    ``observed`` is a ``CompileShapeCache`` (its ``.shapes`` keys), an
    iterable of feeder batches, or an iterable of ``batch_shape_key``
    results.  Each distinct key is one jit compile (the cache's miss
    accounting, core/compiler.py); a laddered feed keeps them bounded by
    rung combinations, so off-ladder extents and key explosion are the two
    churn signatures worth flagging.

    T104 flags only axes whose extent VARIES across the observed keys: a
    static extent (a dense feature width, a fixed batch size) compiles once
    no matter what it is, while a varying axis off the ladder means one
    compile per distinct length — the churn signature."""
    keys = _as_shape_keys(observed)
    rungs = set(ladder) | set(sub_ladder)
    diags: List[Diagnostic] = []

    # per (slot, axis>=1): the set of extents observed across keys
    extents: Dict[Tuple[str, int], set] = {}
    for key in keys:
        for name, shape, _dtype in key:
            for axis, ext in enumerate(shape):
                if axis >= 1:
                    extents.setdefault((name, axis), set()).add(int(ext))

    off: List[str] = []
    for (name, axis), vals in sorted(extents.items()):
        if len(vals) <= 1:
            continue  # static axis: one compile regardless of value
        bad = sorted(
            v for v in vals
            if v > 1 and v not in rungs and not _is_rung_multiple(v, ladder)
        )
        if bad:
            off.append(f"{name} axis {axis}: {bad}")
    if off:
        uniq = sorted(set(off))
        diags.append(Diagnostic(
            rule="T104", severity=Severity.WARNING, source=source,
            message=f"batch shapes pad off the bucketing ladder: {uniq[:5]}"
            + (f" (+{len(uniq) - 5} more)" if len(uniq) > 5 else ""),
            hint="route the feed through reader.bucketing + "
            "DataFeeder(ladder=...) (use_bucketing flag) so every padded "
            "extent is a 16*2^k rung and compiles stay bounded",
        ))

    budget = max_shapes if max_shapes is not None else max(8, 2 * len(ladder))
    if len(keys) > budget:
        diags.append(Diagnostic(
            rule="T105", severity=Severity.WARNING, source=source,
            message=f"{len(keys)} distinct batch shapes observed (budget "
            f"{budget}) — the step recompiles per batch, not per rung",
            hint="enable bucketing, pin drop_last=True, or tie the "
            "token-budget batcher to the dominant sequence slot so rung "
            "combinations collapse",
        ))
    return diags


def _is_rung_multiple(ext: int, ladder: Sequence[int]) -> bool:
    """Past the top rung, ladder_len canonicalizes to multiples of it."""
    top = ladder[-1] if ladder else 0
    return bool(top) and ext > top and ext % top == 0


def _as_shape_keys(observed) -> List[tuple]:
    shapes = getattr(observed, "shapes", None)
    if isinstance(shapes, dict):  # CompileShapeCache
        return list(shapes)
    keys = []
    for item in observed:
        if isinstance(item, tuple) and item and isinstance(item[0], tuple):
            keys.append(item)  # already a shape key
        else:
            keys.append(batch_shape_key(item))
    return list(dict.fromkeys(keys))
