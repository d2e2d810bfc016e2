"""Divergence-localizing numerics sanitizer — the runtime leg of the
numerics plane.

The divergence sentinel (robustness/sentinel.py + the fused device half in
trainer/step.py) detects that a step went non-finite and skips it — but it
cannot say WHICH op produced the first NaN/inf, so a ``nan_batch`` chaos
drill ends as "a step was skipped" instead of "this feed slot poisoned
that dot".  This module closes the gap the way the lock sanitizer closed
it for deadlocks: armed via ``PADDLE_TPU_NUM_SANITIZER=1`` (the
``num_sanitizer`` flag), the trainer keeps a host copy of each step's
inputs BEFORE the donated dispatch consumes them, and when the sentinel
flags a step, the step's jaxpr is re-executed **equation by equation**
through a small interpreter on the captured batch:

* the first eqn whose output is non-finite is named, with layer
  provenance from the named-scope stack (the T100 note plane's
  vocabulary) and source provenance from ``eqn.source_info``;
* call-like eqns (jit / custom-vjp), ``scan`` (stepped iteration by
  iteration) and ``cond`` (the taken branch) are descended into, so the
  record points at a primitive, not at "the scan";
* every input of the offending eqn gets max-abs / non-finite-count
  stats folded into StatSet ``num/<eqn>`` rows (the guarded
  ``StatSet.observe`` keeps non-finite observations in their own
  bucket), and the whole postmortem rides the PR-13 flight-recorder
  dump (``flight-<pid>.json``, ``otherData.numerics``).

Unarmed, the training path is untouched: no captures, no copies, no
extra dispatches — counter-asserted in tests (``num_sanitizer/captures``
stays zero) and byte-identical params either way (the sanitizer only
observes; it never changes the step).
"""

from __future__ import annotations

import logging
import math
import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu.utils.timers import global_stats

__all__ = ["NumericsSanitizer", "num_sanitizer_armed", "find_first_nonfinite"]

_log = logging.getLogger("paddle_tpu.analysis.num_sanitizer")

ENV_FLAG = "PADDLE_TPU_NUM_SANITIZER"


def num_sanitizer_armed() -> bool:
    """The ``num_sanitizer`` flag (environment: ``PADDLE_TPU_NUM_SANITIZER``);
    tolerant of a stripped flags plane."""
    try:
        from paddle_tpu.utils import flags as _flags

        return bool(_flags.get_flag("num_sanitizer"))
    except KeyError:  # pragma: no cover — stripped deployment
        return os.environ.get(ENV_FLAG, "").lower() in ("1", "true", "yes")


# ---------------------------------------------------------------------------
# the eqn-by-eqn interpreter
# ---------------------------------------------------------------------------


class _Found(Exception):
    """Raised by the interpreter at the first non-finite-producing eqn;
    carries the postmortem record."""

    def __init__(self, record: Dict[str, Any]):
        super().__init__(record.get("primitive", "?"))
        self.record = record


def _is_inexact(x) -> bool:
    dt = getattr(x, "dtype", None)
    if dt is None:
        return False
    # jnp.issubdtype: ml_dtypes floats (bfloat16/f8) are not numpy
    # inexact subtypes, and a bf16 NaN must not slip past the check
    import jax.numpy as jnp

    return jnp.issubdtype(np.dtype(dt), jnp.inexact)


def _nonfinite(x) -> bool:
    if not _is_inexact(x):
        return False
    arr = np.asarray(x)
    return bool(arr.size) and not bool(np.isfinite(arr).all())


def _val_stats(x) -> Dict[str, Any]:
    """Shape/dtype/max-abs/non-finite-count summary of one value."""
    out: Dict[str, Any] = {
        "shape": list(np.shape(x)),
        "dtype": str(getattr(x, "dtype", type(x).__name__)),
    }
    try:
        arr = np.asarray(x, dtype=np.float64) if _is_inexact(x) else None
    except (TypeError, ValueError):
        arr = None
    if arr is not None and arr.size:
        finite = arr[np.isfinite(arr)]
        out["max_abs"] = float(np.abs(finite).max()) if finite.size else None
        out["n_nonfinite"] = int(arr.size - finite.size)
    return out


def _bind(eqn, invals: Sequence[Any]):
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    outs = eqn.primitive.bind(*subfuns, *invals, **bind_params)
    return list(outs) if eqn.primitive.multiple_results else [outs]


def _call_prims() -> frozenset:
    """The lint's call-like primitive set — ONE list, so the lint seeing
    through a call and the postmortem localizing into it never diverge."""
    from paddle_tpu.analysis.numerics_lint import _INLINE_PRIMS

    return _INLINE_PRIMS


def _sub_closed_jaxprs(params: Dict[str, Any]):
    from paddle_tpu.analysis.numerics_lint import _sub_jaxprs

    return _sub_jaxprs(params)


def _record(eqn, invals, outs, path: str, idx: int) -> Dict[str, Any]:
    from paddle_tpu.analysis.numerics_lint import _eqn_layer, _eqn_site

    src, line = _eqn_site(eqn)
    return {
        "eqn": f"{path}{idx}:{eqn.primitive.name}",
        "primitive": eqn.primitive.name,
        "layer": _eqn_layer(eqn),
        "source": src,
        "line": line,
        "inputs": [_val_stats(x) for x in invals],
        "outputs": [_val_stats(x) for x in outs],
    }


def _eval_jaxpr(jaxpr, consts, args, path: str) -> List[Any]:
    """Evaluate ``jaxpr`` eqn by eqn; raises :class:`_Found` at the first
    eqn whose output holds a NaN/inf, after localizing INTO call-like /
    scan / cond eqns so the record names a primitive, not a region."""
    from jax.extend.core import Literal

    env: Dict[Any, Any] = {}

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    for var, val in zip(jaxpr.constvars, consts):
        env[var] = val
    for var, val in zip(jaxpr.invars, args):
        env[var] = val
    for idx, eqn in enumerate(jaxpr.eqns):
        invals = [read(v) for v in eqn.invars]
        outs = _bind(eqn, invals)
        if any(_nonfinite(o) for o in outs):
            raise _Found(_localize(eqn, invals, outs, path, idx))
        for var, val in zip(eqn.outvars, outs):
            env[var] = val
    return [read(v) for v in jaxpr.outvars]


def _localize(eqn, invals, outs, path: str, idx: int) -> Dict[str, Any]:
    prim = eqn.primitive.name
    here = f"{path}{idx}:{prim}/"
    try:
        if prim in _call_prims():
            for sub in _sub_closed_jaxprs(eqn.params):
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                if len(inner.invars) == len(invals):
                    try:
                        _eval_jaxpr(inner, list(getattr(sub, "consts", ())),
                                    invals, here)
                    except _Found as f:
                        return f.record
                    break
        elif prim == "scan":
            rec = _localize_scan(eqn, invals, here)
            if rec is not None:
                return rec
        elif prim == "cond":
            branches = eqn.params.get("branches", ())
            pred = int(np.asarray(invals[0]))
            if 0 <= pred < len(branches):
                sub = branches[pred]
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                if len(inner.invars) == len(invals) - 1:
                    try:
                        _eval_jaxpr(inner, list(getattr(sub, "consts", ())),
                                    invals[1:], here + f"branch{pred}/")
                    except _Found as f:
                        return f.record
    except _Found:
        raise
    except Exception:  # noqa: BLE001 — localization is best-effort
        _log.debug("sub-localization failed at %s%d:%s", path, idx, prim,
                   exc_info=True)
    return _record(eqn, invals, outs, path, idx)


def _localize_scan(eqn, invals, here: str) -> Optional[Dict[str, Any]]:
    """Step a scan's body iteration by iteration to find the first
    non-finite-producing step AND eqn inside it."""
    params = eqn.params
    sub = params.get("jaxpr")
    if sub is None:
        return None
    inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
    n_consts = int(params.get("num_consts", 0))
    n_carry = int(params.get("num_carry", 0))
    length = int(params.get("length", 0))
    reverse = bool(params.get("reverse", False))
    consts = invals[:n_consts]
    carry = list(invals[n_consts:n_consts + n_carry])
    xs = invals[n_consts + n_carry:]
    steps = range(length - 1, -1, -1) if reverse else range(length)
    for t in steps:
        xsl = [np.asarray(x)[t] for x in xs]
        try:
            outs = _eval_jaxpr(
                inner, list(getattr(sub, "consts", ())),
                list(consts) + carry + xsl, f"{here}step{t}/",
            )
        except _Found as f:
            f.record["scan_step"] = t
            return f.record
        carry = list(outs[:n_carry])
    return None


def find_first_nonfinite(fn, args) -> Optional[Dict[str, Any]]:
    """Trace ``fn`` on ``args`` and re-execute its jaxpr eqn-by-eqn;
    returns the postmortem record of the first non-finite-producing eqn
    (with ``poisoned_inputs`` naming any arg leaves that were ALREADY
    non-finite — the poisoned-feed case), or None when every value stays
    finite."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    flat: List[Any] = []
    labels: List[str] = []
    for argnum, a in enumerate(args):
        for pth, leaf in jax.tree_util.tree_leaves_with_path(a):
            flat.append(leaf)
            labels.append(f"arg{argnum}{jax.tree_util.keystr(pth)}")
    if len(flat) != len(closed.jaxpr.invars):
        flat = jax.tree_util.tree_leaves(args)
        labels = [f"in{i}" for i in range(len(flat))]
    poisoned = [
        {"input": lbl, **_val_stats(v)}
        for lbl, v in zip(labels, flat) if _nonfinite(v)
    ]
    try:
        _eval_jaxpr(closed.jaxpr, list(closed.consts), flat, "")
    except _Found as f:
        rec = f.record
        rec["poisoned_inputs"] = poisoned
        return rec
    if poisoned:
        # inputs were poisoned but nothing downstream blew up (masked away)
        return {"eqn": None, "primitive": None, "poisoned_inputs": poisoned,
                "inputs": [], "outputs": []}
    return None


# ---------------------------------------------------------------------------
# the trainer-facing sanitizer
# ---------------------------------------------------------------------------


class NumericsSanitizer:
    """Pre-step input capture + postmortem driver for one trainer.

    ``step_body`` is the UN-jitted single-step computation (the same
    ``_train_step_body`` the jitted step compiles), traced fresh on the
    captured arguments — host-side re-execution, no donation, no effect
    on the training trajectory."""

    def __init__(self, step_body, stats=None):
        self._step_body = step_body
        self._stats = stats if stats is not None else global_stats
        self._captured = None
        self._where = ""

    @classmethod
    def for_trainer(cls, trainer) -> "NumericsSanitizer":
        from paddle_tpu.trainer.step import _train_step_body

        # sentinel=False: the postmortem wants the raw computation — the
        # per-leaf select that protects params on device would otherwise
        # sit between the first NaN and the metrics
        body = _train_step_body(
            trainer.network, trainer.optimizer, trainer._metrics_fn,
            trainer._prune_masks, sentinel=False,
        )
        return cls(body)

    def capture(self, params, state, opt_state, batch, rng,
                where: str = "") -> None:
        """Host-copy this step's inputs BEFORE the donated dispatch
        invalidates them.  Armed-mode cost only; the unarmed trainer
        never constructs this object."""
        import jax

        self._stats.incr("num_sanitizer/captures")
        self._captured = jax.device_get((params, state, opt_state, batch, rng))
        self._where = where

    def postmortem(self, reason: str) -> Optional[Dict[str, Any]]:
        """Re-execute the captured step eqn-by-eqn and dump the numerics
        postmortem into the flight recorder.  Never raises."""
        if self._captured is None:
            return None
        try:
            rec = find_first_nonfinite(self._step_body, self._captured)
        except Exception:  # noqa: BLE001 — a postmortem must never crash
            _log.exception("numerics postmortem failed (%s)", reason)
            return None
        if rec is None:
            _log.warning(
                "numerics sanitizer: %s but the re-executed step is "
                "finite everywhere (non-determinism or fetch-side issue)",
                reason,
            )
            return None
        rec["reason"] = reason
        rec["where"] = self._where
        tag = rec.get("eqn") or "input-only"
        for j, s in enumerate(rec.get("inputs", ())):
            if s.get("max_abs") is not None:
                self._stats.observe(f"num/{tag}/in{j}_max_abs", s["max_abs"])
            if s.get("n_nonfinite"):
                self._stats.observe(f"num/{tag}/in{j}_max_abs", math.nan)
        _log.error(
            "numerics postmortem (%s): first non-finite at %s layer=%s "
            "%s:%s poisoned=%s", reason, tag, rec.get("layer"),
            rec.get("source"), rec.get("line"),
            [p["input"] for p in rec.get("poisoned_inputs", ())],
        )
        from paddle_tpu import obs as _obs

        _obs.flight_dump(f"num-sanitizer: {reason}",
                         extra={"numerics": rec})
        return rec
