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
    """Follows the reference through one Adam step for each of the batches.

    What it holds on the device, in float32 copies of the parameter set: `w`,
    the gradient that is being summed, and one transient (a block's gradient
    before it is added; one leaf's moments during the update), plus one
    block's activations.  Adam's `m` and `v` wait on the host while a
    gradient is computed, the first step's gradient goes there as the
    program's did, and the weights before the first step are not kept: they
    are drawn again for the change.

    fault: None, or one of the faults a training cell can have, planted in
    the reference put in the program's place:
      "half_batch"   the second half of every batch left out, the mean taken
                     over the rest
      "no_exchange"  (cells over several chips) the first chip's shard of
                     every batch alone, as a step without its all-reduce
                     leaves each chip

    watch: called with no argument where the most is held (a block's gradient
    just computed, a leaf just updated), for whoever counts the bytes.
    """

    def __init__(self, block_cost, opt, block_rows, precision="float32",
                 fault=None, chips=1, cell="", watch=None):
        mm = MATMULS[precision]
        self._grad = jax.jit(jax.value_and_grad(
            lambda w, blk: block_cost(w, blk, mm)))
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                            donate_argnums=0)
        self._block_rows = block_rows
        self._fault = fault
        self._chips = chips
        self._cell = cell
        self._watch = watch or (lambda: None)
        b1, b2, eps, lr = (opt["beta1"], opt["beta2"], opt["epsilon"],
                           opt["learning_rate"])

        def adam_leaf(w, m, v, g, t, inv_rows):
            g = g * inv_rows
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            w = w - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            return w, m, v, g

        self._adam_leaf = jax.jit(adam_leaf, donate_argnums=(0, 1, 2, 3))

    def _batch_grad(self, w, batch):
        n = rows = len(next(iter(batch.values())))
        if self._fault == "half_batch":
            n //= 2
        elif self._fault == "no_exchange":
            n //= self._chips
        if n == 0:
            raise ValueError(f"{self._cell}: the fault {self._fault} leaves no row "
                             f"of this mix's batch of {rows}")
        r = min(self._block_rows, n)
        if n % r:
            raise ValueError(f"{self._cell}: {n} rows do not divide into blocks of {r}")
        total, grads = 0.0, None
        for lo in range(0, n, r):
            blk = {k: jnp.asarray(v) for k, v in _rows(batch, lo, lo + r).items()}
            c, g = self._grad(w, blk)
            total += float(c)
            self._watch()
            grads = g if grads is None else self._add(grads, g)
            del g  # or the next block's gradient would find this one still held
        return total / n, grads, n

    def _update(self, w, m, v, g, t, n, first):
        """Adam on every leaf in turn, in place in w, m, v; g is used up.
        With `first`, the gradient as Adam got it goes there: name -> (its
        norm, the leaf on the host)."""
        for k in sorted(w):
            w[k], mk, vk, gk = self._adam_leaf(w[k], m[k], v[k], g.pop(k), float(t), 1.0 / n)
            self._watch()
            # np.array copies: on the CPU np.asarray would be a view that
            # keeps the device's buffer
            m[k], v[k] = np.array(mk), np.array(vk)
            if first is not None:
                first[k] = (leaf_norm(gk), np.array(gk))

    def run(self, draw, batches):
        """draw() -> the weights, the same at every call.  -> dict: losses
        [steps], grad (first step, per leaf, on the host) with its
        grad_norms, change_norms (after the last step)."""
        w = draw()
        m = {k: np.zeros(x.shape, np.float32) for k, x in w.items()}
        v = {k: np.zeros(x.shape, np.float32) for k, x in w.items()}
        losses, first = [], {}
        for t, batch in enumerate(batches, start=1):
            loss, g, n = self._batch_grad(w, batch)
            losses.append(loss)
            self._update(w, m, v, g, t, n, first if t == 1 else None)
        return {"losses": losses, "grad": {k: x for k, (_, x) in first.items()},
                "grad_norms": {k: norm for k, (norm, _) in first.items()},
                "change_norms": leaf_norms(w, draw())}


@jax.jit
def _norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


@jax.jit
def _norms(a, b):
    return {k: _norm(a[k], b[k]) for k in a}


def leaf_norm(a, b=None):
    """One leaf's norm of a, or of a - b, as a Python float: sets that wait on
    the host come to the device a leaf at a time."""
    return float(_norm(a, jnp.zeros((), a.dtype) if b is None else b))


def leaf_norms(a, b=None):
    """Each leaf's norm of a, or of a - b, as Python floats, the whole set in
    one call."""
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
    diff = {k: leaf_norm(got["grad"][k], ref["grad"][k]) for k in got["grad"]}
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
