"""Drives a configuration's plain reference through the first training steps
and compares the program's readings with it.  Imports nothing of the program.

The reference's block_cost gives the summed cost of a block of rows; here the
blocks' gradients are added up so a whole batch fits beside the activations,
divided by the rows (the cost is the mean over the rows of each row's
token-summed cross entropy), and plain Adam with bias correction is applied.
"""

import importlib.util
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_by_name(folder, name):
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- how a weight matmul is computed: the reference's way, and the control's --

def mm_float32(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _scaled(x, dtype, top):
    """x as an 8-bit float type holds it under a per-tensor scale that puts
    its largest magnitude on the type's largest, `top`."""
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def mm_fp8(a, b):
    """The control: the step below the bfloat16 that the configurations
    state, as fp8 training is done: every weight matmul with both operands in
    float8_e4m3, and in the backward pass the incoming gradient in
    float8_e5m2; sums in float32."""
    return mm_float32(_scaled(a, jnp.float8_e4m3fn, 448.0),
                      _scaled(b, jnp.float8_e4m3fn, 448.0))


def _mm_fp8_fwd(a, b):
    return mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(operands, g):
    a, b = (_scaled(x, jnp.float8_e4m3fn, 448.0) for x in operands)
    g = _scaled(g, jnp.float8_e5m2, 57344.0)
    rows = a.reshape(-1, a.shape[-1])
    return mm_float32(g, b.T), mm_float32(rows.T, g.reshape(-1, g.shape[-1]))


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


MATMULS = {"float32": mm_float32, "fp8": mm_fp8}


def _rows(batch, lo, hi):
    return {k: v[lo:hi] for k, v in batch.items()}


class ReferenceRun:
    """Follows `steps` Adam steps of the reference on the given batches.

    fault: None, or one of the faults a training cell can have, planted in
    the reference put in the program's place:
      "half_batch"   the second half of every batch left out, the mean taken
                     over the rest
      "no_exchange"  (cells over several chips) the first chip's shard of
                     every batch alone, as a step without its all-reduce
                     leaves each chip
    """

    def __init__(self, block_cost, opt, block_rows, precision="float32",
                 fault=None, chips=1):
        mm = MATMULS[precision]
        self._grad = jax.jit(jax.value_and_grad(
            lambda w, blk: block_cost(w, blk, mm)))
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        self._block_rows = block_rows
        self._fault = fault
        self._chips = chips
        b1, b2, eps, lr = (opt["beta1"], opt["beta2"], opt["epsilon"],
                           opt["learning_rate"])

        def adam(w, m, v, g, t, inv_rows):
            def leaf(w, m, v, g):
                g = g * inv_rows
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * jnp.square(g)
                w = w - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                return w, m, v, g
            out = {k: leaf(w[k], m[k], v[k], g[k]) for k in w}
            return tuple({k: o[i] for k, o in out.items()} for i in range(4))

        self._adam = jax.jit(adam, donate_argnums=(0, 1, 2))

    def _batch_grad(self, w, batch):
        n = len(next(iter(batch.values())))
        if self._fault == "half_batch":
            n //= 2
        elif self._fault == "no_exchange":
            n //= self._chips
        r = min(self._block_rows, n)
        if n % r:
            raise ValueError(f"{n} rows do not divide into blocks of {r}")
        total, grads = 0.0, None
        for lo in range(0, n, r):
            blk = {k: jnp.asarray(v) for k, v in _rows(batch, lo, lo + r).items()}
            c, g = self._grad(w, blk)
            total += float(c)
            grads = g if grads is None else self._add(grads, g)
        return total / n, grads, n

    def run(self, weights, batches):
        """-> dict: losses [steps], grad (first step, per leaf, on the
        device) with its grad_norms, change_norms (after the last step)."""
        w0 = weights
        w = {k: jnp.copy(x) for k, x in weights.items()}
        m = {k: jnp.zeros_like(x) for k, x in w.items()}
        v = {k: jnp.zeros_like(x) for k, x in w.items()}
        losses, grad = [], None
        for t, batch in enumerate(batches, start=1):
            loss, g, n = self._batch_grad(w, batch)
            losses.append(loss)
            w, m, v, g = self._adam(w, m, v, g, float(t), 1.0 / n)
            if t == 1:
                grad = g
        return {"losses": losses, "grad": grad, "grad_norms": leaf_norms(grad),
                "change_norms": leaf_norms(w, w0)}


@jax.jit
def _norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a}


def leaf_norms(a, b=None):
    """Each leaf's norm of a, or of a - b, as Python floats."""
    if b is None:
        b = {k: jnp.zeros((), x.dtype) for k, x in a.items()}
    return {k: float(x) for k, x in _norms(a, b).items()}


def worst_leaf_gap(got, ref, leaves=None, diff=None):
    """The widest gap between the two norms of one leaf (or, with `diff`,
    the largest norm of the two leaves' difference), as a share of the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    leaves = sorted(ref) if leaves is None else leaves
    floor = statistics.median(ref[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        gap = (abs(got[k] - ref[k]) if diff is None else diff[k]) / max(ref[k], floor, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def compare(got, ref):
    """The numbers that decide `correct`, each the widest over its steps or
    leaves.  Leaves whose first gradient in the reference is under a
    thousandth of the median leaf's move under Adam by round-off alone and
    are left out of the change."""
    loss_gap = max(
        (abs(a - b) / abs(b)) if np.isfinite(a) else float("inf")
        for a, b in zip(got["losses"], ref["losses"])
    )
    grad_gap, grad_leaf = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    moved = [k for k in sorted(ref["grad_norms"]) if ref["grad_norms"][k] >= 1e-3 * med]
    change_gap, change_leaf = worst_leaf_gap(
        got["change_norms"], ref["change_norms"], moved)
    diff = leaf_norms(got["grad"], ref["grad"])
    diff_gap, diff_leaf = worst_leaf_gap(None, ref["grad_norms"], diff=diff)
    shares = sorted(diff[k] / max(ref["grad_norms"][k], med, 1e-30) for k in diff)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": grad_gap,
        "change_norm_gap": change_gap,
        "grad_diff": diff_gap,
    }, {"grad_leaf": grad_leaf, "change_leaf": change_leaf, "grad_diff_leaf": diff_leaf,
        "grad_diff_median_leaf": statistics.median(shares),
        "left_out_of_change": sorted(set(ref["grad_norms"]) - set(moved))}
