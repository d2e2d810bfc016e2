"""Topology → pure JAX function compiler.

This replaces the reference's runtime layer-graph interpreter
(``NeuralNetwork::forward`` looping over C++ Layer objects, reference:
paddle/gserver/gradientmachines/NeuralNetwork.cpp:235-292) with a trace-time
loop: :meth:`CompiledNetwork.apply` walks the topology **while being traced by
jax.jit**, so the emitted program is one fused XLA computation per step —
the OpDesc→HLO lowering the north star asks for.  Gradients come from
``jax.grad`` over the whole step instead of per-layer ``backward``.

State handling: trainable parameters and non-trainable state (batch-norm
moving stats — the reference mutates these inside forward,
paddle/gserver/layers/BatchNormBaseLayer.h) are separate pytrees; ``apply``
returns updated state functionally.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.batch import Batch, SeqTensor, batch_shape_key
from paddle_tpu.core.topology import Topology
from paddle_tpu.layers.base import ApplyContext, get_layer_impl, stable_hash
from paddle_tpu.ops.activations import apply_activation

Params = Dict[str, Dict[str, Any]]
NetState = Dict[str, Dict[str, Any]]

# Global default compute dtype for newly-built networks.  Master parameters
# always live in float32; when this is bfloat16 the forward/backward compute
# runs in bf16 on the MXU (mixed precision — the cast's transpose upcasts
# gradients back to f32 for the optimizer).  Set via paddle.init or
# settings(), queried at CompiledNetwork construction.
_default_compute_dtype = None


def set_default_compute_dtype(dtype) -> None:
    global _default_compute_dtype
    _default_compute_dtype = None if dtype is None else jnp.dtype(dtype)


def get_default_compute_dtype():
    return _default_compute_dtype


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _error_clip(x, t):
    """Identity forward; the backward clips the layer-output cotangent to
    [-t, t] (reference ExtraLayerAttribute.error_clipping_threshold,
    Layer.cpp backwardActivation)."""
    return x


def _error_clip_fwd(x, t):
    return x, None


def _error_clip_bwd(t, _res, g):
    return (jnp.clip(g, -t, t),)


_error_clip.defvjp(_error_clip_fwd, _error_clip_bwd)


def _cast_floats(tree, dtype):
    """Cast every floating leaf of a pytree to `dtype` (ints/bools pass)."""
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)  # num: allow[N406] the mixed contract quantizes EVERY non-full-precision layer output at its boundary, even when an f32 consumer follows — downstream must see the same values a fully-bf16 pipeline produces
        return x

    return jax.tree_util.tree_map(cast, tree)


def _feed_transform(conf, t):
    """On-device narrow-dtype feed (DataProvider.h double-buffer parity —
    the reference never ships float32 pixels either; mnist_bin_part stores
    uint8).  A DENSE slot arriving at an integer dtype (the DataFeeder's
    ``feed_dtypes`` wire form) is cast to float32 here, INSIDE the jitted
    step, and normalized with the data layer's feed_scale/feed_shift attrs
    — XLA fuses the cast+scale into the first consumer, so the host->device
    transfer is 1/4 the bytes with zero extra kernels."""
    from paddle_tpu.core.data_types import SlotKind

    it = conf.input_type
    data = t.data if hasattr(t, "data") else t
    if (
        it is None
        or it.kind != SlotKind.DENSE
        or not jnp.issubdtype(data.dtype, jnp.integer)
    ):
        return t
    x = data.astype(jnp.float32)
    scale = conf.attr("feed_scale") or 0.0
    shift = conf.attr("feed_shift") or 0.0
    if scale:
        x = x * scale
    if shift:
        x = x + shift
    return SeqTensor(x, getattr(t, "lengths", None),
                     getattr(t, "sub_lengths", None))


def _walk_layers(topology, prefix=()):
    """(path, conf) over a topology INCLUDING recurrent_group sub-topologies
    (path = (top_layer, inner..., layer)) — the traversal behind the global
    parameter table: named parameters share storage wherever they live, like
    the reference's per-name Parameter map (config_parser.py Parameters /
    gserver's global parameter table), including inside recurrent groups."""
    for name in topology.order:
        conf = topology.layers[name]
        yield prefix + (name,), conf
        sub = conf.attrs.get("_sub_topology")
        if sub is not None:
            yield from _walk_layers(sub, prefix + (name,))


def _get_path(d, path):
    for k in path:
        d = d[k]
    return d


def _set_path(d, path, v):
    """Set d[path] with copy-on-write of every intermediate dict (the caller
    has already shallow-copied `d` itself), so grafting shared values never
    mutates the canonical params tree."""
    cur = d
    for k in path[:-1]:
        nxt = dict(cur.get(k, {}))
        cur[k] = nxt
        cur = nxt
    cur[path[-1]] = v


def _share_conflict_error(message: str, layer: str):
    """Parameter-sharing conflict in the shared diagnostic format (rule
    G006 — same id the graph linter reports for these, so config-time and
    build-time findings read identically).  DiagnosticError subclasses
    ValueError: every existing except/raises site keeps working."""
    from paddle_tpu.analysis.diagnostics import (
        Diagnostic,
        DiagnosticError,
        Severity,
    )

    return DiagnosticError(Diagnostic(
        rule="G006",
        severity=Severity.ERROR,
        layer=layer,
        message=message,
        hint="give the parameters distinct ParamAttr names, or align the "
        "declaring layers' shapes/forms",
    ))


def _mixed_forms_error(key_owners, g, path, decl) -> ValueError:
    """Mixed whole-layer/per-key declaration of one global parameter name."""
    ol, ok, owhole = key_owners[g]
    kind = "whole-layer inside a recurrent_group" if owhole else "per-key"
    return _share_conflict_error(
        f"parameter name {g!r} is declared {decl} by {'.'.join(path)!r} but "
        f"{kind} by {ol!r}.{'.'.join(ok)!r}; sharing across the two forms "
        "is not supported — use distinct names",
        ".".join(path),
    )


def _del_path(d, path):
    """Delete d[path], pruning dicts emptied by the deletion."""
    stack = []
    cur = d
    for k in path[:-1]:
        stack.append((cur, k))
        cur = cur[k]
    del cur[path[-1]]
    for parent, k in reversed(stack):
        if not parent[k]:
            del parent[k]


class CompileShapeCache:
    """Host-side mirror of the jit executable cache, keyed per bucket shape.

    jax.jit keys its cache by abstract argument shapes; the feed layer
    controls exactly one slice of that key — the batch's slot shapes
    (core.batch.batch_shape_key).  Observing every batch here makes the
    compile behaviour of a variable-length feed visible and testable:

    * hit/miss counters export through the StatSet plane (``<name>/
      compile_hit`` / ``compile_miss`` in utils.timers.global_stats — the
      same table REGISTER_TIMER stats print in), so a feed that recompiles
      per batch shows up in the stats instead of as mystery latency;
    * ``n_shapes`` asserts the shape-ladder contract: with a laddered feed
      (reader.bucketing + DataFeeder(ladder=...)), every padded extent is a
      ladder rung, so distinct shapes across an epoch are bounded by the
      combinations of slot rungs the data actually realizes — one per rung
      when slot lengths correlate, and never a shape per batch, instead of
      growing with the length distribution.  (Multiple sequence slots with
      UNcorrelated lengths multiply rung combinations; pass the batcher a
      ``key``/``slots`` tied to the dominant slot if that bites.)
    """

    def __init__(self, name: str = "train_step", stats=None):
        from paddle_tpu.utils.timers import global_stats

        self.name = name
        self._stats = stats if stats is not None else global_stats
        self.shapes: Dict[tuple, int] = {}  # shape key -> dispatch count

    def observe(self, batch: Batch) -> bool:
        """Record one dispatch; True when this shape is new (a compile)."""
        key = batch_shape_key(batch)
        miss = key not in self.shapes
        self.shapes[key] = self.shapes.get(key, 0) + 1
        self._stats.incr(
            f"{self.name}/compile_{'miss' if miss else 'hit'}"
        )
        return miss

    @property
    def n_shapes(self) -> int:
        return len(self.shapes)

    @property
    def misses(self) -> int:
        # by construction every distinct shape missed exactly once
        return self.n_shapes

    @property
    def hits(self) -> int:
        return sum(self.shapes.values()) - len(self.shapes)

    def summary(self) -> Dict[str, int]:
        return {
            "shapes": self.n_shapes,
            "hits": self.hits,
            "misses": self.misses,
        }


class CompiledNetwork:
    """init/apply view over a Topology."""

    def __init__(self, topology: Topology, dtype=jnp.float32, compute_dtype=None):
        self.topology = topology
        self.dtype = dtype
        # Mesh handed to mesh-aware layers via ApplyContext; the trainer
        # sets this so ring attention traces against ITS mesh instead of a
        # process-global (two trainers with different meshes stay isolated).
        self.mesh = None
        if compute_dtype is None:
            compute_dtype = _default_compute_dtype or dtype
        self.compute_dtype = jnp.dtype(compute_dtype)
        # Resolve implementations eagerly so unknown types fail at build.
        self._impls = {
            name: get_layer_impl(conf.type)
            for name, conf in topology.layers.items()
        }
        # Cross-layer parameter sharing by ParamAttr name (the reference's
        # global parameter table: two layers declaring the same parameter
        # name share storage — e.g. crf + crf_decoding sharing "crfw",
        # tied embeddings).  First declarer in topology order owns the
        # params; later declarers read the owner's slot.  Two granularities:
        #   attr("param_name")  — the whole layer param dict (legacy layers
        #                         with one logical parameter);
        #   attr("param_names") — {param_key: global_name} per-key sharing
        #                         (fc per-input weights, mixed projections,
        #                         named bias attrs) — including intra-layer
        #                         duplicates like fc param_attr=[p, p].
        # _shared_keys: sharer top-level layer -> {relpath: (owner top-level
        # layer, owner relpath)}.  relpath is a tuple of dict keys into the
        # layer's param subtree — one element for a flat layer key, longer
        # for parameters inside a recurrent_group's nested params (and a
        # whole inner-layer dict for legacy one-parameter layers inside a
        # group).  Sharing WITHIN one group's subtree is handled by that
        # group's own sub-CompiledNetwork running this same scan.
        self._param_owner: Dict[str, str] = {}
        self._shared_keys: Dict[str, Dict[tuple, tuple]] = {}
        # global parameter table: reference parameters are NAMED objects
        # (Parameter.h:46; v2 parameters.get("embedding.w0")) — map each
        # declared global name to its owning storage path (top layer,
        # relpath-into-its-param-subtree)
        self._named_params: Dict[str, tuple] = {}
        owners: Dict[str, str] = {}
        key_owners: Dict[str, tuple] = {}
        inner_seen: set = set()  # (global name, top layer) with an inner decl
        for path, conf in _walk_layers(topology):
            name, rel = path[0], tuple(path[1:])
            pmap = conf.attr("param_names") or {}
            pname = conf.attr("param_name")
            if pname and not pmap:
                if not rel:
                    if pname in key_owners:
                        raise _mixed_forms_error(
                            key_owners, pname, path, "whole-layer"
                        )
                    if pname in owners:
                        self._param_owner[name] = owners[pname]
                    else:
                        owners[pname] = name
                        self._named_params[pname] = (name, ())
                else:
                    # legacy one-parameter layer inside a group: share its
                    # whole inner dict at `rel`
                    if pname in owners:
                        raise _share_conflict_error(
                            f"parameter name {pname!r} is declared whole-layer "
                            f"both at top level ({owners[pname]!r}) and inside "
                            f"a recurrent_group ({'.'.join(path)!r}); use "
                            "distinct names",
                            ".".join(path),
                        )
                    if pname in key_owners and not key_owners[pname][2]:
                        raise _mixed_forms_error(
                            key_owners, pname, path,
                            "whole-layer inside a recurrent_group",
                        )
                    owner = self._inner_key_owner(
                        key_owners, inner_seen, pname, name, rel,
                        inner=True, whole=True,
                    )
                    if owner is not None:
                        self._shared_keys.setdefault(name, {})[rel] = owner
                    else:
                        self._named_params.setdefault(pname, (name, rel))
            for key, gname in pmap.items():
                if not gname:
                    continue
                if gname in owners:
                    raise _share_conflict_error(
                        f"parameter name {gname!r} is declared per-key by "
                        f"{'.'.join(path)!r}.{key!r} but whole-layer by "
                        f"{owners[gname]!r}; sharing across the two layer "
                        "kinds is not supported — use distinct names",
                        ".".join(path),
                    )
                if gname in key_owners and key_owners[gname][2]:
                    raise _mixed_forms_error(key_owners, gname, path, "per-key")
                kp = rel + (key,)
                owner = self._inner_key_owner(
                    key_owners, inner_seen, gname, name, kp,
                    inner=bool(rel), whole=False,
                )
                if owner is not None:
                    self._shared_keys.setdefault(name, {})[kp] = owner
                else:
                    self._named_params.setdefault(gname, (name, kp))

    @staticmethod
    def _inner_key_owner(key_owners, inner_seen, gname, top, relpath, inner,
                         whole):
        """First declarer of `gname` wins ownership; a later declarer gets
        the owner's address back — except a second declaration INSIDE the
        same top-level layer's subtree, where the group's own sub-network
        scan already chains it to the subtree's first declarer (returning
        None avoids double handling — and that first declarer is itself
        grafted from the global owner, so the chain stays correct even when
        the global owner lives outside the subtree)."""
        if inner:
            if (gname, top) in inner_seen:
                return None  # sub-CompiledNetwork chains this to the first
            inner_seen.add((gname, top))
        if gname not in key_owners:
            key_owners[gname] = (top, relpath, whole)
            return None
        otop, orel, _ = key_owners[gname]
        return (otop, orel)

    # ------------------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Params:
        params: Params = {}
        for name in self.topology.order:
            conf = self.topology.layers[name]
            impl = self._impls[name]
            in_confs = [self.topology.layers[i] for i in conf.inputs]
            layer_rng = jax.random.fold_in(rng, stable_hash(name))
            p = impl.init(conf, in_confs, layer_rng)
            owner = self._param_owner.get(name)
            if owner is not None:
                # sharer: storage lives at the owner — validate the shapes
                # agree NOW so a name collision between differently-sized
                # layers fails at build, not deep inside a matmul
                want = jax.tree_util.tree_map(jnp.shape, p)
                have = jax.tree_util.tree_map(jnp.shape, params.get(owner, {}))
                if want != have:
                    raise _share_conflict_error(
                        f"shares parameter {conf.attr('param_name')!r} with "
                        f"{owner!r} but expects shapes {want} != owner's "
                        f"{have}",
                        name,
                    )
                continue
            for relpath, (ol, orel) in self._shared_keys.get(name, {}).items():
                owner_val = (
                    _get_path(p, orel) if ol == name
                    else _get_path(params[ol], orel)
                )
                mine = _get_path(p, relpath)
                want = jax.tree_util.tree_map(jnp.shape, mine)
                have = jax.tree_util.tree_map(jnp.shape, owner_val)
                if want != have:
                    raise _share_conflict_error(
                        f"parameter {'.'.join(relpath)!r} shares storage "
                        f"with {ol!r}.{'.'.join(orel)!r} but expects shapes "
                        f"{want} != owner's {have}",
                        name,
                    )
                _del_path(p, relpath)
            if p:
                params[name] = p
        return params

    # ------------------------------------------------------------------
    def row_bound_slots(self) -> Tuple[str, ...]:
        """The data slots whose lengths this network's recurrent_groups with
        a hoisted epilogue scan: a feed that puts a RowBound on them
        (reader.feeder.DataFeeder) lets those groups run the epilogue on
        the rows that hold a token alone.  Empty for a network without such
        a group, whose batches then carry no bound."""
        from paddle_tpu.layers.recurrent_group import row_bound_slots

        return row_bound_slots(self.topology)

    @property
    def has_dynamic_widths(self) -> bool:
        """Any fc / matrix projection stacked on a dynamic-width input
        (whole-minibatch trans, TransLayer.cpp) — their true weight height
        is the runtime batch size."""
        for conf in self.topology.layers.values():
            if conf.attr("dynamic_width_in"):
                return True
            for s in conf.attrs.get("projections", ()):
                if s.get("dynamic_width"):
                    return True
        return False

    def resolve_dynamic_widths(
        self, params: Params, batch: Batch, seed: int = 0
    ) -> Tuple[Params, bool]:
        """Re-initialize weights whose height depends on the runtime batch
        size, now that a batch exists.

        A whole-minibatch ``trans`` (reference TransLayer.cpp) outputs
        [D, B]: a consuming fc/matrix-projection weight must be [B, size],
        but B is unknowable at init, so init builds the declared static
        size (matching the reference's parameter dims — which can then only
        RUN at batch == size, protostr test_fc dims 100x100).  The trainer
        calls this with its first batch; weights whose height mismatches
        the actual B are re-drawn (deterministically from ``seed``) at the
        right shape and the optimizer state must be rebuilt by the caller
        when ``changed`` comes back True.  Note the inherent semantics of
        batch-wide transpose: weights trained at one batch size cannot be
        reused at another (true of the op, not this implementation) — feed
        with drop_last=True so a ragged final batch doesn't change B.
        Weights restored at a shape matching neither the static init nor
        this batch raise (they trained at another B); the one blind spot
        is a checkpoint trained at exactly B == the declared static size,
        which is indistinguishable from a fresh init by shape."""
        import dataclasses

        b = 0
        for t in batch.values():
            data = t.data if hasattr(t, "data") else t
            b = int(data.shape[0])
            break
        if not b:
            return params, False
        rng = jax.random.PRNGKey(seed)
        out = dict(params)
        changed = False
        for name in self.topology.order:
            conf = self.topology.layers[name]
            dyn_fc = conf.attr("dynamic_width_in") or ()
            dyn_proj = [
                j for j, s in enumerate(conf.attrs.get("projections", ()))
                if s.get("dynamic_width")
            ]
            if not dyn_fc and not dyn_proj:
                continue
            in_confs = [self.topology.layers[i] for i in conf.inputs]
            patched = list(in_confs)
            targets = set(dyn_fc) | {
                conf.attrs["projections"][j]["in"] for j in dyn_proj
            }
            for i in targets:
                # the dynamic input's runtime width is the batch size B
                # (trans swaps [B, D] -> [D, B]); width-preserving unaries
                # in between keep it
                patched[i] = dataclasses.replace(in_confs[i], size=b)
            impl = self._impls[name]
            layer_rng = jax.random.fold_in(rng, stable_hash(name))
            fresh = impl.init(conf, patched, layer_rng)
            # what a FRESH (untrained) init looks like at the declared
            # static sizes — only weights still in that state may be
            # re-drawn; anything else was trained/restored at some other
            # batch size and re-drawing it would silently destroy it
            static_init = impl.init(conf, in_confs, layer_rng)
            cur = dict(out.get(name, {}))
            layer_changed = False
            for k, v in fresh.items():
                if k not in cur or jnp.shape(cur[k]) == jnp.shape(v):
                    continue
                if jnp.shape(cur[k]) != jnp.shape(static_init.get(k)):
                    raise ValueError(
                        f"layer {name!r} parameter {k!r} has shape "
                        f"{jnp.shape(cur[k])} — neither the declared static "
                        f"shape {jnp.shape(static_init.get(k))} nor this "
                        f"batch's resolved shape {jnp.shape(v)}.  It was "
                        "trained/restored at a different batch size; "
                        "batch-wide-trans weights are only usable at the "
                        "batch size they trained at."
                    )
                cur[k] = v
                layer_changed = True
            if layer_changed:
                out[name] = cur
                changed = True
        return out, changed

    def init_state(self) -> NetState:
        state: NetState = {}
        for name in self.topology.order:
            conf = self.topology.layers[name]
            impl = self._impls[name]
            if impl.init_state is not None:
                in_confs = [self.topology.layers[i] for i in conf.inputs]
                s = impl.init_state(conf, in_confs)
                if s:
                    state[name] = s
        return state

    def init(self, rng: jax.Array) -> Tuple[Params, NetState]:
        return self.init_params(rng), self.init_state()

    # ------------------------------------------------------------------
    def make_context(self, *, train: bool, rng=None, state=None) -> ApplyContext:
        """ApplyContext exactly as apply() would build it (mesh fallback
        included) — shared with utils.debug so diagnostics trace the same
        computation as training."""
        from paddle_tpu.parallel.mesh import get_default_mesh

        return ApplyContext(
            train=train,
            rng=rng,
            state=state or {},
            dtype=self.compute_dtype,
            mesh=self.mesh if self.mesh is not None else get_default_mesh(),
        )

    # ------------------------------------------------------------------
    def layer_params(self, params: Params, name: str):
        """This layer's effective param dict: owner lookup for whole-layer
        sharing plus per-key grafts of shared storage (copy-on-write — the
        canonical params tree is never mutated)."""
        p = params.get(self._param_owner.get(name, name), {})
        shared = self._shared_keys.get(name)
        if shared:
            p = dict(p)
            for relpath, (ol, orel) in shared.items():
                src = (
                    _get_path(p, orel) if ol == name
                    else _get_path(params[ol], orel)
                )
                _set_path(p, relpath, src)
        return p

    def named_parameters(self) -> Dict[str, str]:
        """Global parameter table: {declared parameter name: dotted storage
        path into the params tree} (reference Parameter.h:46 named buffers /
        v2 parameters surface — the reference addresses every parameter by
        its config-declared name)."""
        return {
            gname: ".".join((top,) + tuple(rel))
            for gname, (top, rel) in self._named_params.items()
        }

    def materialize_shared(self, params: Params) -> Params:
        """Params with every shared key grafted back in place, per top-level
        layer.  For feeding a sub-network or pruned subgraph that was
        compiled WITHOUT this network's sharing maps (e.g. generation-time
        decoder stepping reads params['decoder'] directly)."""
        out: Params = {}
        for name in self.topology.order:
            p = self.layer_params(params, name)
            if p:
                out[name] = p
        return out

    def resolve_layer_call(self, name: str, params: Params, ins):
        """(layer params, inputs) as the apply loop would hand them to the
        impl: shared-parameter owner lookup + mixed-precision casts.  Used
        by apply() and by utils.debug.profile_layers so the profiler times
        exactly what training runs."""
        impl = self._impls[name]
        p = self.layer_params(params, name)
        if self.compute_dtype != jnp.dtype(jnp.float32):
            if impl.full_precision:
                ins = [_cast_floats(x, jnp.float32) for x in ins]
            else:
                p = _cast_floats(p, self.compute_dtype)
                ins = [_cast_floats(x, self.compute_dtype) for x in ins]
        return p, ins

    # ------------------------------------------------------------------
    def apply(
        self,
        params: Params,
        batch: Batch,
        *,
        state: Optional[NetState] = None,
        train: bool = True,
        rng: Optional[jax.Array] = None,
        only: Optional[set] = None,
        preset: Optional[Dict[str, SeqTensor]] = None,
    ) -> Tuple[Dict[str, SeqTensor], NetState]:
        """Run the whole graph; returns every layer's output by name plus the
        functionally-updated state.

        `only` restricts execution to the named layers (everything else is
        skipped — its output must then come from `preset` if a survivor
        needs it); `preset` seeds layer outputs directly.  Both exist for
        recurrent_group's epilogue hoisting: the scan body executes the
        loop partition, the stacked epilogue partition runs once outside
        with the loop's outputs preset."""
        mixed = self.compute_dtype != jnp.dtype(jnp.float32)
        # Mixed precision: master params and the raw batch stay f32; each
        # non-full_precision layer casts its own params/inputs to the compute
        # dtype below.  Casting the whole batch up front would quantize float
        # regression targets / soft labels before the full_precision cost
        # layers ever see them.
        ctx = self.make_context(train=train, rng=rng, state=state)
        if preset:
            ctx.outputs.update(preset)
        for name in self.topology.order:
            if preset and name in preset:
                continue
            if only is not None and name not in only:
                continue
            conf = self.topology.layers[name]
            impl = self._impls[name]
            # named_scope labels everything a layer costs in profiler traces
            # under "type:name": a data slot's on-device feed transform
            # (data:<slot>), a layer's body with the activation/dropout/clip
            # this loop applies after it, and beside it (cast:<layer>, NOT
            # inside: readers that time a layer by its scope keep timing the
            # layer) mixed precision's casts of its weights and inputs — no
            # operation of the graph runs outside such a scope.
            if conf.type in ("data", "step_input", "memory"):
                # data: user slots; step_input/memory: placeholders fed by an
                # enclosing recurrent_group's scan body.
                if name not in batch:
                    raise KeyError(f"batch is missing data slot {name!r}")
                with jax.named_scope(f"{conf.type}:{name}"):
                    ctx.outputs[name] = _feed_transform(conf, batch[name])
                continue
            ins = [ctx.outputs[i] for i in conf.inputs]
            pre_keys = set(ctx.outputs) if mixed else ()
            with jax.named_scope(f"cast:{name}"):
                p, ins = self.resolve_layer_call(name, params, ins)
            with jax.named_scope(f"{conf.type}:{name}"):
                # the except-note is the CustomStackTrace equivalent (reference
                # utils/CustomStackTrace.h:51 pushes layer names so a fatal
                # error reports which layer it happened in).
                try:
                    out = impl.apply(conf, p, ins, ctx)
                except Exception as e:
                    # layer-provenance note in the shared diagnostic format
                    # (analysis.diagnostics) — trace-time shape errors read like
                    # the graph linter's config-time findings, naming the layer
                    from paddle_tpu.analysis.diagnostics import (
                        Diagnostic,
                        Severity,
                    )

                    shapes = [getattr(t.data, "shape", None) for t in ins]
                    note = Diagnostic(
                        rule="T100",
                        severity=Severity.ERROR,
                        layer=name,
                        message=(
                            f"failed while applying this layer (type={conf.type}, "
                            f"size={conf.size}, inputs={list(conf.inputs)} with "
                            f"shapes {shapes})"
                        ),
                        hint="run analysis.graph_lint.lint_topology on this "
                        "topology — most shape/arity mistakes are caught "
                        "before tracing",
                    ).format()
                    e.add_note(note)
                    raise
                if mixed and not impl.full_precision:
                    # Enforce the compute dtype at every layer boundary —
                    # f32 constants/masks inside an impl would otherwise promote
                    # and leak float32 downstream (breaking e.g. scan carries).
                    out = _cast_floats(out, self.compute_dtype)
                    for k in set(ctx.outputs) - pre_keys:  # side outputs (@cell, …)
                        ctx.outputs[k] = _cast_floats(
                            ctx.outputs[k], self.compute_dtype
                        )
                if impl.auto_activation and conf.act not in ("identity", "linear", ""):
                    if conf.act == "softmax":
                        # Stash pre-activation logits so downstream cross_entropy
                        # fuses into log-softmax CE (numerically stable); XLA
                        # dead-code-eliminates this when unused.
                        ctx.outputs[name + "@logits"] = out
                    mask = out.mask() if (out.is_seq and conf.act == "sequence_softmax") else None
                    out = out.with_data(apply_activation(conf.act, out.data, mask))
                if impl.auto_dropout and conf.drop_rate > 0.0 and train:
                    drop_rng = ctx.layer_rng(name + "/dropout")
                    if drop_rng is not None:
                        keep = 1.0 - conf.drop_rate
                        m = jax.random.bernoulli(drop_rng, keep, out.data.shape)
                        out = out.with_data(
                            jnp.where(m, out.data / keep, jnp.zeros_like(out.data))
                        )
                eclip = conf.attr("error_clip", 0.0)
                if eclip and train:
                    out = out.with_data(_error_clip(out.data, eclip))
                ctx.outputs[name] = out
        new_state = dict(ctx.state)
        new_state.update(ctx.new_state)
        return ctx.outputs, new_state

    # ------------------------------------------------------------------
    def forward(
        self,
        params: Params,
        batch: Batch,
        *,
        state: Optional[NetState] = None,
        train: bool = True,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[SeqTensor, Dict[str, SeqTensor], NetState]:
        """First declared output, the full output dict, and updated state."""
        outs, new_state = self.apply(params, batch, state=state, train=train, rng=rng)
        return outs[self.topology.output_names[0]], outs, new_state

    def cost(
        self,
        params: Params,
        batch: Batch,
        *,
        state: Optional[NetState] = None,
        rng: Optional[jax.Array] = None,
        train: bool = True,
    ):
        """(scalar mean cost, (outputs, new_state)) — the differentiable
        quantity (replaces GradientMachine::backward's sum-of-cost seeding,
        reference: paddle/gserver/gradientmachines/GradientMachine.h:72)."""
        out, outs, new_state = self.forward(
            params, batch, state=state, train=train, rng=rng
        )
        name = self.topology.output_names[0]
        # the batch mean is the cost layer's last operation: its scope
        with jax.named_scope(f"{self.topology.layers[name].type}:{name}"):
            return jnp.mean(out.data), (outs, new_state)


def count_params(params: Params) -> int:
    return sum(int(jnp.size(x)) for x in jax.tree_util.tree_leaves(params))
