"""The benchmark's own weights: one jitted call from the seed, on the device,
float32 (the master type the configurations state).  The program's parameters
and the reference's arguments are both filled from this one dict, keyed by the
reference's argument names."""

import math

import jax
import jax.numpy as jnp


def make_weights(shapes, seed):
    """shapes: name -> (shape, "normal" | "zeros" | "ones"); normal is
    N(0, 1/sqrt(rows))."""
    names = sorted(shapes)

    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, law = shapes[name]
            if law == "normal":
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                ) * (1.0 / math.sqrt(shape[0]))
            elif law == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    # the seed goes in as data, so one compiled program serves every seed
    return jax.jit(lambda s: draw(jax.random.PRNGKey(s)))(
        jnp.uint32(seed % (2 ** 32))
    )


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
    """Nested dict as the program's Parameters.params holds it; each leaf a
    copy, because the train step donates its arguments."""
    tree = {}
    for ref, path in param_map.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = jnp.copy(weights[ref])
    return tree


def from_program_tree(tree, param_map):
    out = {}
    for ref, path in param_map.items():
        node = tree
        for p in path.split("/"):
            node = node[p]
        out[ref] = node
    return out
