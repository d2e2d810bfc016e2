"""Train/eval step builders — the replacement for the reference's
TrainerInternal::trainOneBatch + GradientMachine forward/backward + per-param
updater callback pipeline (reference: paddle/trainer/TrainerInternal.cpp:66-190).

One call = one jitted XLA computation: forward, jax.grad backward, gradient
psum across the data mesh axis (implicit via sharding), optimizer update, and
metric reduction all fuse into a single program with donated buffers, so
parameters update in place on device — no host round-trip per batch (the
reference crosses Python↔SWIG each batch, v2/trainer.py:145-161).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.compiler import CompiledNetwork, NetState, Params
from paddle_tpu.optimizer import Optimizer, OptState
from paddle_tpu.parallel.mesh import DATA_AXIS


def build_prune_masks(network: CompiledNetwork, params: Params) -> Optional[Params]:
    """Static pruning masks (reference StaticPruningHook,
    ParameterUpdaterHook.cpp:39): for every layer whose ParamAttr declared a
    'pruning' hook, keep the largest (1 - sparsity_ratio) fraction of each
    parameter by INITIAL magnitude; the train step re-applies the mask after
    every update.  Returns None when nothing prunes."""
    masks: Params = {}
    for name, conf in network.topology.layers.items():
        ratio = conf.attr("prune_sparsity")
        if not ratio:
            continue
        # a layer sharing parameters by name stores them under the owner
        name = network._param_owner.get(name, name)
        if name not in params or name in masks:
            continue

        def mask_leaf(v, r=ratio):
            flat = jnp.abs(v).reshape(-1)
            k = max(int(flat.shape[0] * (1.0 - r)), 1)
            thresh = jax.lax.top_k(flat, k)[0][-1]
            return (jnp.abs(v) >= thresh).astype(v.dtype)

        # hooks attach to the WEIGHT parameter (the reference's ParamAttr is
        # per-parameter; bias has its own attr) — prune w* leaves only
        masks[name] = {
            k: (mask_leaf(v) if k.startswith("w") else jnp.ones_like(v))
            for k, v in params[name].items()
        }
    return masks or None


def apply_prune_masks(params: Params, masks: Optional[Params]) -> Params:
    if not masks:
        return params
    out = dict(params)
    for name, m in masks.items():
        out[name] = jax.tree_util.tree_map(
            lambda p, mk: p * mk.astype(p.dtype), params[name], m
        )
    return out


def _global_norm(grads) -> jnp.ndarray:
    """float32 l2 norm over every gradient leaf — one fused reduction; any
    NaN/Inf leaf makes the result non-finite, so finiteness of this single
    scalar is the whole-tree health signal."""
    leaves = jax.tree_util.tree_leaves(grads)
    if not leaves:
        return jnp.asarray(0.0, jnp.float32)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    )


def _sentinel_enabled(sentinel: Optional[bool]) -> bool:
    if sentinel is not None:
        return bool(sentinel)
    from paddle_tpu.utils.flags import get_flag

    return bool(get_flag("divergence_sentinel"))


def _quantized_enabled(quantized: Optional[bool]) -> bool:
    if quantized is not None:
        return bool(quantized)
    from paddle_tpu.utils.flags import get_flag

    return bool(get_flag("quantized_allreduce"))


def _update_and_guard(
    optimizer: Optimizer, prune_masks, guard: bool, grads, cost,
    params, state, new_state, opt_state,
):
    """The step's tail after the gradients, shared by the plain and the
    quantized-allreduce bodies: optimizer update (+ prune masks), then the
    divergence sentinel's finiteness test and per-leaf selects.  Each half
    traces under a ``type:name`` scope of its own (``optimizer:<method>``,
    ``guard:sentinel``), as every layer does, so a device trace can say
    what the update and the guard cost.
    -> (new_params, new_state, new_opt_state, metrics)"""
    update_scope = f"optimizer:{type(optimizer).__name__.lower()}"
    with jax.named_scope(update_scope):
        new_params, new_opt_state = optimizer.update(grads, opt_state, params)
        new_params = apply_prune_masks(new_params, prune_masks)
    metrics = {"cost": cost}
    if guard:
        with jax.named_scope("guard:sentinel"):
            grad_norm = _global_norm(grads)
            healthy = jnp.isfinite(cost.astype(jnp.float32)) & jnp.isfinite(
                grad_norm
            )

            def keep(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jax.lax.select(healthy, n, o), new, old
                )

            new_state = keep(new_state, state)
            # XLA names a fused kernel after its root operation, and the
            # update's kernels end in these selects: they trace under the
            # optimizer's scope too, or a device trace would lay the whole
            # update at the guard's door
            with jax.named_scope(update_scope):
                new_params = keep(new_params, params)
                new_opt_state = keep(new_opt_state, opt_state)
            metrics["health"] = healthy.astype(jnp.float32)
            metrics["grad_norm"] = grad_norm
    return new_params, new_state, new_opt_state, metrics


def _train_step_body(
    network: CompiledNetwork,
    optimizer: Optimizer,
    extra_metrics=None,
    prune_masks: Optional[Params] = None,
    sentinel: Optional[bool] = None,
):
    """The un-jitted single-step computation shared by make_train_step and
    make_multi_train_step: forward, grad, optimizer update, metrics.

    sentinel (None = the ``divergence_sentinel`` flag): fuse a finiteness
    check of the loss and the gradient global-norm into the step.  The
    ``health`` flag (1.0 = finite) rides the metrics — no extra host sync —
    and an unhealthy step passes params / layer state / optimizer state
    through UNCHANGED (per-leaf select), so one NaN batch is a skipped step,
    not a corrupted run (robustness/sentinel.py is the host-side judge)."""
    guard = _sentinel_enabled(sentinel)

    def step(params, state, opt_state, batch, rng):
        def loss_fn(p):
            return network.cost(p, batch, state=state, rng=rng, train=True)

        (cost, (outs, new_state)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        new_params, new_state, new_opt_state, metrics = _update_and_guard(
            optimizer, prune_masks, guard, grads, cost,
            params, state, new_state, opt_state,
        )
        if extra_metrics is not None:
            metrics.update(extra_metrics(outs))
        return new_params, new_state, new_opt_state, metrics

    return step


def make_quantized_train_step(
    network: CompiledNetwork,
    optimizer: Optimizer,
    mesh: Mesh,
    extra_metrics: Optional[
        Callable[[Dict[str, Any]], Dict[str, jnp.ndarray]]
    ] = None,
    prune_masks: Optional[Params] = None,
    sentinel: Optional[bool] = None,
):
    """The ``quantized_allreduce`` train step: same signature and metric
    surface as :func:`make_train_step`, but the data-axis gradient
    reduction is an EXPLICIT block-scaled quantized collective
    (ops/quantize.py :func:`~paddle_tpu.ops.quantize.quantized_psum`)
    instead of the implicit f32 psum XLA SPMD inserts.

    Structure: a ``shard_map`` over the (pure data-parallel) mesh computes
    per-shard gradients, then psums the int8/bf16 payload blocks AND their
    f32 scales side-by-side — the exact region shape rule N405 certifies —
    and dequantizes to the gradient mean; cost pmeans at f32; per-row
    layer outputs reassemble across the data axis so ``extra_metrics``
    still sees the whole batch.  The optimizer update, prune masks and the
    divergence sentinel run on the reduced (replicated) gradients exactly
    as in the baseline body, so everything downstream of the allreduce is
    shared.

    Payload dtype / block size / stochastic rounding come from the
    ``quantize_*`` flags at build time."""
    import numpy as np

    from paddle_tpu.ops.quantize import quantized_psum
    from paddle_tpu.utils.flags import get_flag

    if mesh.shape.get("model", 1) != 1:
        raise ValueError(
            "quantized_allreduce needs a pure data-parallel mesh "
            f"(model axis is {mesh.shape.get('model')}); quantize only "
            "the data-axis gradient reduction"
        )
    guard = _sentinel_enabled(sentinel)
    payload_dtype = jnp.dtype(str(get_flag("quantize_payload_dtype")))
    block = int(get_flag("quantize_block_size"))
    stochastic = bool(get_flag("quantize_stochastic_rounding"))
    # collapse to a 1-axis data mesh over the same devices in the same
    # order: shard_map wants every mesh axis named in its specs
    qmesh = Mesh(np.array(mesh.devices).reshape(-1), (DATA_AXIS,))

    def shard_grads(params, state, batch, rng):
        def loss_fn(p):
            return network.cost(p, batch, state=state, rng=rng, train=True)

        (cost, (outs, new_state)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        grads = quantized_psum(
            grads, DATA_AXIS, block=block, payload_dtype=payload_dtype,
            stochastic=stochastic, rng=(rng if stochastic else None),
            mean=True,
        )
        cost = jax.lax.pmean(cost.astype(jnp.float32), DATA_AXIS)
        # out_specs joins the shards' outputs along axis 0, which puts
        # batch-major values together again and nothing else: a group's
        # "@logits_rows" (its rows' order is not the batch's) stays inside
        outs = {k: v for k, v in outs.items() if isinstance(v, SeqTensor)}
        return grads, cost, new_state, outs

    smapped = jax.shard_map(
        shard_grads, mesh=qmesh,
        in_specs=(P(), P(), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P(), P(DATA_AXIS)),
        check_vma=False,  # per-shard state/dropout outs: replication is by
        # construction of the deterministic update, not provable statically
    )

    def step(params, state, opt_state, batch, rng):
        grads, cost, new_state, outs = smapped(params, state, batch, rng)
        new_params, new_state, new_opt_state, metrics = _update_and_guard(
            optimizer, prune_masks, guard, grads, cost,
            params, state, new_state, opt_state,
        )
        if extra_metrics is not None:
            metrics.update(extra_metrics(outs))
        return new_params, new_state, new_opt_state, metrics

    repl = NamedSharding(qmesh, P())
    batch_sh = NamedSharding(qmesh, P(DATA_AXIS))
    return jax.jit(
        step,
        donate_argnums=(0, 1, 2),
        in_shardings=(repl, repl, repl, batch_sh, repl),
        out_shardings=(repl, repl, repl, repl),
    )


def make_train_step(
    network: CompiledNetwork,
    optimizer: Optimizer,
    mesh: Optional[Mesh] = None,
    extra_metrics: Optional[
        Callable[[Dict[str, Any]], Dict[str, jnp.ndarray]]
    ] = None,
    infer_param_shardings: bool = False,
    prune_masks: Optional[Params] = None,
    sentinel: Optional[bool] = None,
    quantized: Optional[bool] = None,
):
    """Returns jitted
    (params, state, opt_state, batch, rng) ->
        (params, state, opt_state, metrics).

    With infer_param_shardings=True the params/opt_state shardings follow the
    argument placement (use parallel.sharding.shard_params first) so
    model-axis-sharded tables stay sharded through the update; otherwise
    params are pinned replicated.  sentinel: see _train_step_body.

    quantized (None = the ``quantized_allreduce`` flag): with a data-
    parallel mesh, route the gradient reduction through the block-scaled
    quantized collective (:func:`make_quantized_train_step`).  OFF is the
    byte-for-byte historical path — no graph change whatsoever.  Without
    a mesh there is no cross-device reduction to quantize and the flag is
    a no-op."""
    if (
        _quantized_enabled(quantized)
        and mesh is not None
        and not infer_param_shardings
    ):
        return make_quantized_train_step(
            network, optimizer, mesh, extra_metrics,
            prune_masks=prune_masks, sentinel=sentinel,
        )
    step = _train_step_body(
        network, optimizer, extra_metrics, prune_masks, sentinel=sentinel
    )

    if mesh is None or infer_param_shardings:
        # No mesh, or sharding flows from the arguments (batch via
        # shard_batch, params via shard_params); XLA SPMD inserts the
        # psum/all-gathers.
        return jax.jit(step, donate_argnums=(0, 1, 2))

    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(DATA_AXIS))
    return jax.jit(
        step,
        donate_argnums=(0, 1, 2),
        in_shardings=(repl, repl, repl, batch_sh, repl),
        out_shardings=(repl, repl, repl, repl),
    )


def make_multi_train_step(
    network: CompiledNetwork,
    optimizer: Optimizer,
    n_steps: int,
    mesh: Optional[Mesh] = None,
    extra_metrics: Optional[
        Callable[[Dict[str, Any]], Dict[str, jnp.ndarray]]
    ] = None,
    prune_masks: Optional[Params] = None,
    sentinel: Optional[bool] = None,
):
    """``n_steps`` train steps in ONE dispatch: lax.scan of the single-step
    body over batches stacked on a leading [n_steps, ...] axis.

    Returns jitted (params, state, opt_state, stacked_batches, rng) ->
    (params, state, opt_state, last-step metrics).

    Why: every dispatch crosses the host->device boundary once; where
    dispatch latency rivals step time the loop measures the host, not the
    chip.  Folding K steps amortizes that cost K-fold, which is also how a
    production input pipeline behaves locally (async dispatch keeps the
    device queue full).
    The reference's TrainerBenchmark loop has no such boundary — its
    trainOneBatch is a C++ call.

    With the sentinel on, each scanned step skips independently on device;
    the returned metrics fold the whole dispatch: ``health`` is the MIN over
    the K steps and ``skipped_steps`` counts the dropped ones, so a fetch
    every K dispatches still sees every skip."""
    step = _train_step_body(
        network, optimizer, extra_metrics, prune_masks, sentinel=sentinel
    )

    def multi(params, state, opt_state, batches, rng):
        rngs = jax.random.split(rng, n_steps)

        def body(carry, xs):
            p, s, o = carry
            b, r = xs
            p, s, o, m = step(p, s, o, b, r)
            return (p, s, o), m

        (p, s, o), ms = jax.lax.scan(
            body, (params, state, opt_state), (batches, rngs)
        )
        out = jax.tree_util.tree_map(lambda x: x[-1], ms)
        if "health" in ms:
            out["health"] = jnp.min(ms["health"])
            out["skipped_steps"] = jnp.sum(1.0 - ms["health"])
        return p, s, o, out

    if mesh is None:
        return jax.jit(multi, donate_argnums=(0, 1, 2))
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(None, DATA_AXIS))
    return jax.jit(
        multi,
        donate_argnums=(0, 1, 2),
        in_shardings=(repl, repl, repl, batch_sh, repl),
        out_shardings=(repl, repl, repl, repl),
    )


def make_grad_step(
    network: CompiledNetwork,
    mesh: Optional[Mesh] = None,
    infer_param_shardings: bool = False,
):
    """Returns jitted ``(params, state, batch, rng) -> (grads, cost)`` —
    the gradient HALF of the train step, with no optimizer update fused in.

    This is the unit of work of the elastic multi-process trainer
    (trainer/elastic.py): each leased data-shard task contributes one
    deterministic gradient tree, the fleet reduces the contributions in
    task-id order at the pass fence, and every process applies the SAME
    reduced update — so the result is bit-identical however tasks were
    distributed, which is what lets a killed worker's shards requeue to
    survivors without perturbing the trajectory.  Layer-state updates (BN
    statistics etc.) from the forward pass are intentionally dropped:
    pass-synchronous reduction has no per-step state stream to thread."""

    def gstep(params, state, batch, rng):
        def loss_fn(p):
            return network.cost(p, batch, state=state, rng=rng, train=True)

        (cost, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return grads, cost

    if mesh is None or infer_param_shardings:
        return jax.jit(gstep)
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(DATA_AXIS))
    return jax.jit(
        gstep,
        in_shardings=(repl, repl, batch_sh, repl),
        out_shardings=repl,
    )


def make_eval_step(
    network: CompiledNetwork,
    mesh: Optional[Mesh] = None,
    extra_metrics: Optional[
        Callable[[Dict[str, Any]], Dict[str, jnp.ndarray]]
    ] = None,
    infer_param_shardings: bool = False,
):
    """(params, state, batch) -> metrics (test-time, no dropout/BN update)."""

    def step(params, state, batch):
        cost, (outs, _) = network.cost(params, batch, state=state, train=False)
        metrics = {"cost": cost}
        if extra_metrics is not None:
            metrics.update(extra_metrics(outs))
        return metrics

    if mesh is None or infer_param_shardings:
        return jax.jit(step)
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(DATA_AXIS))
    return jax.jit(
        step, in_shardings=(repl, repl, batch_sh), out_shardings=repl
    )


def make_forward_fn(network: CompiledNetwork, output_names=None):
    """Inference forward returning selected layer outputs (the capi /
    Inference equivalent, reference paddle/capi/gradient_machine.h:60)."""

    @functools.partial(jax.jit, static_argnames=("train",))
    def fwd(params, state, batch, train=False):
        outs, _ = network.apply(params, batch, state=state, train=train)
        names = output_names or network.topology.output_names
        return {n: outs[n].data for n in names}

    return fwd
