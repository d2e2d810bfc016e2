"""The benchmark's own weights: one jitted call from the seed, on the device,
float32 (the master type the configurations state).  The program's parameters
and the reference's arguments are both filled from this one draw, keyed by the
reference's argument names."""

import math

import jax
import jax.numpy as jnp


def _draw_leaf(key, shape, law):
    """One leaf under its law.  "normal" is N(0, 1/sqrt(shape[0])), right for
    a matrix whose rows are its fan-in; a leaf of another layout (an
    expert-major [E, D, H]) states its fan-in, ("normal", fan_in);
    ("constant", c) fills with c (a state-space layer's time-step bias, the
    logarithm of its decay)."""
    if law == "ones":
        return jnp.ones(shape, jnp.float32)
    if law == "zeros":
        return jnp.zeros(shape, jnp.float32)
    name, value = ("normal", shape[0]) if law == "normal" else law
    if name == "normal":
        return jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(value))
    if name == "constant":
        return jnp.full(shape, value, jnp.float32)
    raise ValueError(f"no draw law {law!r}")


def drawer(shapes):
    """shapes: name -> (shape, law), as a reference's `param_shapes` gives
    them.  -> draw(seed): one jitted call that makes every leaf on the
    device.  The seed goes in as data, so one compiled program serves every
    seed, and the same seed gives the same bits again: the benchmark keeps no
    copy of its draw beside the program's, it draws a second time."""
    names = sorted(shapes)

    @jax.jit
    def draw(seed):
        key = jax.random.PRNGKey(seed)
        return {name: _draw_leaf(jax.random.fold_in(key, i), *shapes[name])
                for i, name in enumerate(names)}

    return lambda seed: draw(jnp.uint32(seed % (2 ** 32)))


def expand_param_map(cfg):
    """The configuration's map reference-argument -> program path, with
    `{i}` entries repeated over the layers."""
    n = cfg.get(cfg.get("param_map_per_layer", ""), 0)
    out = {}
    for ref, prog in cfg["param_map"].items():
        if "{i}" in ref:
            for i in range(n):
                out[ref.format(i=i)] = prog.format(i=i)
        else:
            out[ref] = prog
    return out


def to_program_tree(weights, param_map):
    """Nested dict as the program's Parameters.params holds it.  The leaves
    themselves, no copies: the train step donates its arguments, so whoever
    calls hands a draw over and keeps none of it."""
    tree = {}
    for ref, path in param_map.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = weights[ref]
    return tree


def from_program_tree(tree, param_map):
    out = {}
    for ref, path in param_map.items():
        node = tree
        for p in path.split("/"):
            node = node[p]
        out[ref] = node
    return out
