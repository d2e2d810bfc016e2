"""layer_loop — a group of layers that runs `n_steps` times over its own
output with ONE set of weights (the looped / universal-transformer stack:
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741).

    x^0 = input;  x^t = step(x^{t-1}), t = 1..n_steps, the same weights in
    every pass;  output = x^{n_steps};  `<name>@passes` = [x^1 .. x^n]

Lowering: the step function is traced ONCE at model-build time into a
sub-topology, with recurrent_group's plumbing (a `step_input` placeholder,
`_trace_capture`, the `_sub_topology` attribute the compiler's parameter
table and the linters already walk).  At apply time the sub-network is the
body of ONE `lax.scan` of length n_steps whose carry is the [B, T, D]
sequence; the weights are the scan's constants, so the scan's transpose sums
their gradients over the passes.  An unrolled topology of n_steps x the
layers would be traced, lowered and compiled n_steps times over, kernels
included.

Recomputation: the backward keeps each pass's INPUT and runs the pass's
forward again (`scan(jax.checkpoint(pass))`), the blocked attention kernels'
own residuals with the rest.  Without it a backward holds every pass's
activations: for a gated-MLP decoder layer about 10 D-wide and 3 F-wide
bfloat16 arrays a token (75 kB at D 2,048, F 5,632), times layers x passes x
tokens = 9.8 GB at 8 layers, 4 passes, 4,096 tokens.  With it: n_steps kept
inputs (4 x 16.8 MB) and ONE pass's activations at a time (2.5 GB).  The
unit is a pass, not a layer of a pass, because that is what fits
(`PERF.md` section 6, PR 36, has the readings) and the sub-network stays
one traced piece.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import LayerConf, LayerOutput, Topology, auto_name
from paddle_tpu.layers.base import register_layer
from paddle_tpu.layers.recurrent_group import _rg_init, _trace_capture
from paddle_tpu.utils.timers import global_stats


def layer_loop(
    step: Callable[[LayerOutput], LayerOutput],
    input: LayerOutput,
    n_steps: int,
    name: Optional[str] = None,
) -> LayerOutput:
    """Apply `step` (ordinary DSL layers from its one argument to one output
    of the same width) `n_steps` times, each pass on the pass before's
    output, all passes with the same weights.  Returns the last pass's
    output; the stacked outputs of all passes [n_steps, B, T, D] ride the aux
    output ``<name>@passes``.  The group's parameters nest under its name
    (``<name>/<inner layer>/<key>``)."""
    if n_steps < 1:
        raise ValueError(f"layer_loop: n_steps {n_steps} < 1")
    gname = name or auto_name("layer_loop")
    placeholder = LayerConf(
        name=f"{gname}@in", type="step_input", size=input.size, bias=False)
    with _trace_capture() as (gb, _created):
        out = step(LayerOutput(placeholder))
    if gb.memories:
        raise ValueError(
            f"layer_loop {gname!r}: memory() belongs to recurrent_group; a "
            "loop carries its step's output, nothing else")
    if out.size != input.size:
        raise ValueError(
            f"layer_loop {gname!r}: the step maps width {input.size} to "
            f"{out.size}; a pass must be able to start from the last one's output")
    sub_topo = Topology([out])
    outside = [n for n, c in sub_topo.layers.items() if c.type == "data"]
    if outside:
        raise ValueError(
            f"layer_loop {gname!r}: the step reaches the data layers {outside}; "
            "it may use its argument alone")
    from paddle_tpu.core.compiler import CompiledNetwork

    if CompiledNetwork(sub_topo).init_state():
        raise ValueError(
            f"layer_loop {gname!r}: a layer with running state (batch_norm) "
            "has no place in a loop over one set of weights")
    conf = LayerConf(
        name=gname, type="layer_loop", size=out.size, inputs=(input.name,),
        bias=False,
        attrs={
            "_sub_topology": sub_topo,
            "_input": placeholder.name,
            "_output": out.name,
            "n_steps": int(n_steps),
        },
    )
    return LayerOutput(conf, [input])


@register_layer("layer_loop", init=_rg_init, auto_activation=False)
def layer_loop_apply(conf, params, inputs, ctx) -> SeqTensor:
    from paddle_tpu.core.compiler import CompiledNetwork

    a = conf.attrs
    n_steps, in_name, out_name = a["n_steps"], a["_input"], a["_output"]
    subnet = CompiledNetwork(a["_sub_topology"], compute_dtype=ctx.dtype)
    subnet.mesh = ctx.mesh  # the blocked attention kernels ask it for the data axis
    x0 = inputs[0]
    step_rng = ctx.layer_rng(conf.name)

    def one_pass(x, t):
        # fold the pass in so dropout decorrelates across passes
        rng_t = None if step_rng is None else jax.random.fold_in(step_rng, t)
        outs, _ = subnet.apply(params, {in_name: x0.with_data(x)}, train=ctx.train, rng=rng_t)
        y = outs[out_name].data.astype(x.dtype)
        return y, y

    global_stats.incr("loop_passes", n_steps)
    if ctx.train:
        global_stats.incr("loop_recomputed_units", n_steps)
    last, passes = jax.lax.scan(
        jax.checkpoint(one_pass), x0.data, jnp.arange(n_steps, dtype=jnp.uint32))
    ctx.outputs[conf.name + "@passes"] = SeqTensor(passes)
    return x0.with_data(last)
