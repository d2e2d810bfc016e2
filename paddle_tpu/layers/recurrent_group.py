"""recurrent_group — the TPU-native RecurrentGradientMachine (reference:
paddle/gserver/gradientmachines/RecurrentGradientMachine.cpp:530 forward,
python/paddle/trainer_config_helpers/layers.py recurrent_group/memory, and
the SubModelConfig plumbing of config_parser.py:366-386).

Reference semantics: a user step function composed of ordinary layers runs
per timestep; ``memory(name=X)`` reads layer X's output from t-1; sequence
inputs are scanned; non-sequence ("static") inputs are visible every step.
The reference executes this by cloning frame networks per timestep and
re-batching variable-length sequences by length (createInFrameInfo,
.cpp:428).

TPU-native lowering: the step function is traced ONCE at model-build time
into a *sub-topology* (the SubModelConfig analogue).  At apply time the
sub-network becomes the body of one ``lax.scan`` over the padded time axis;
memories are scan carries with mask-carry-through for padding; the whole
group is part of the same jitted XLA program as the rest of the model.
No per-timestep re-batching, no frame cloning — static shapes end to end.
"""

from __future__ import annotations

import contextlib
import warnings
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import LayerConf, LayerOutput, Topology, auto_name
from paddle_tpu.layers.base import ApplyContext, register_layer
from paddle_tpu.ops import acc_einsum
from paddle_tpu.parallel.mesh import DATA_AXIS


class StaticInput:
    """Marks an outer layer as visible-every-step instead of scanned
    (reference StaticInput, trainer_config_helpers/layers.py).  `size` is
    accepted for config compatibility (the reference validates it against
    input.size; here the topology already carries it)."""

    def __init__(self, input: LayerOutput, is_seq: bool = False,
                 size: int = 0):
        self.input = input
        self.is_seq = is_seq
        if size and size != input.size:
            raise ValueError(
                f"StaticInput size {size} != input layer size {input.size}"
            )


class SubsequenceInput:
    """Marks a NESTED outer layer whose subsequences are the scan unit
    (reference SubsequenceInput, trainer_config_helpers/layers.py:3590;
    engine: RecurrentGradientMachine.cpp:428-528 createInFrameInfo with
    hasSubseq).  The group scans the outer S axis; each step's placeholder is
    an ordinary [B, T, ...] sequence, so the step function can itself contain
    sequence layers or an inner recurrent_group (hierarchical RNN)."""

    def __init__(self, input: LayerOutput):
        self.input = input


# Build-time state for the step function trace: maps memory placeholders to
# their link targets so the group layer can wire carries.
class _GroupBuild:
    def __init__(self) -> None:
        self.memories: List[LayerConf] = []
        # placeholder name -> outer boot LayerOutput (must join group parents)
        self.boot_layers: Dict[str, LayerOutput] = {}


_current_build: Optional[_GroupBuild] = None

# Unroll factor for the group scan.  The body is a whole traced
# sub-network; measured on v5e (NMT attention decoder fwd+bwd) unroll=2
# was SLOWER than 1 (33.0 vs 27.9 ms/step) — the body is large enough that
# scan overhead is already amortized and unrolling only bloats the program.
# (The small fused cells in ops/rnn.py are different: they unroll 4x.)
_GROUP_UNROLL = 1


@contextlib.contextmanager
def _group_build():
    global _current_build
    prev = _current_build
    _current_build = _GroupBuild()
    try:
        yield _current_build
    finally:
        _current_build = prev


class _MemoryOutput(LayerOutput):
    """memory() handle: supports the reference's deferred-link form
    ``m = memory(name=None, size=...); ...; m.set_input(layer)``."""

    def set_input(self, layer: LayerOutput) -> None:
        assert self.conf.type == "memory"
        self.conf.attrs["link"] = layer.name


def memory(
    name: Optional[str],
    size: int,
    boot_layer: Optional[LayerOutput] = None,
    boot_with_const_id: Optional[int] = None,
    is_seq: bool = False,
    memory_name: Optional[str] = None,
) -> LayerOutput:
    """Previous-timestep output of the in-group layer called `name`
    (reference memory(), layers.py; RecurrentGradientMachine "memory frame"
    links).  boot_layer provides the t=0 value (non-seq [B, size]).
    name=None defers the link: call ``.set_input(layer)`` before the group
    closes (reference memory(name=None).set_input pattern).

    is_seq=True carries a WHOLE SEQUENCE between outer steps (reference
    sequence-memory frames, RecurrentGradientMachine.cpp:530-608): the step
    sees the linked layer's previous-step [B, T_mem, size] sequence (with
    its lengths), so sequence layers / an inner group can consume it.  The
    boot value is the boot_layer's sequence (or an empty zero-length
    sequence when unbooted); under the static-shape scan the linked layer's
    padded width must be step-invariant."""
    assert _current_build is not None, "memory() must be called inside a recurrent_group step"
    if is_seq and boot_with_const_id is not None:
        raise ValueError(
            "memory(is_seq=True) cannot boot with a constant id — a "
            "sequence memory boots from a sequence boot_layer or as an "
            "empty sequence"
        )
    conf = LayerConf(
        name=auto_name(f"memory_{name or memory_name or 'deferred'}"),
        type="memory",
        size=size,
        bias=False,
        attrs={
            "link": name,
            "boot": boot_layer.name if boot_layer is not None else None,
            "boot_const_id": boot_with_const_id,
            **({"is_seq": True} if is_seq else {}),
        },
    )
    _current_build.memories.append(conf)
    if boot_layer is not None:
        _current_build.boot_layers[conf.name] = boot_layer
    return _MemoryOutput(conf)


@register_layer("memory")
def memory_apply(conf, params, inputs, ctx):  # pragma: no cover
    raise RuntimeError("memory placeholders are fed by the recurrent_group scan")


@register_layer("step_input")
def step_input_apply(conf, params, inputs, ctx):  # pragma: no cover
    raise RuntimeError("step inputs are fed by the recurrent_group scan")


def recurrent_group(
    step,
    input: Union[LayerOutput, StaticInput, Sequence[Union[LayerOutput, StaticInput]]],
    reverse: bool = False,
    name: Optional[str] = None,
) -> LayerOutput:
    """Run `step` over the time axis of the sequence inputs.

    Returns the step's (first) output as a sequence layer.  See module
    docstring for the lowering.
    """
    ins = input if isinstance(input, (list, tuple)) else [input]
    scanned: List[LayerOutput] = []
    sub_scanned: List[bool] = []  # parallel: scan unit is a subsequence
    statics: List[StaticInput] = []
    for i in ins:
        if isinstance(i, StaticInput):
            statics.append(i)
        elif isinstance(i, SubsequenceInput):
            scanned.append(i.input)
            sub_scanned.append(True)
        else:
            scanned.append(i)
            sub_scanned.append(False)
    assert scanned, "recurrent_group needs at least one sequence input to scan"

    gname = name or auto_name("recurrent_group")

    # ---- trace the step function into a sub-topology ------------------
    step_args, scan_placeholders, static_placeholders = _make_placeholders(
        gname, scanned, sub_scanned, statics
    )

    with _trace_capture() as (gb, created):
        out = step(*step_args)
    step_outputs: List[LayerOutput] = out if isinstance(out, (list, tuple)) else [out]
    return _finalize_group(
        gname, scanned, sub_scanned, statics, scan_placeholders,
        static_placeholders, gb, created, step_outputs, reverse,
    )


@contextlib.contextmanager
def _trace_capture():
    """Group-trace context shared by the step-function face above and the
    raw RecurrentLayerGroupBegin/End face: opens a _GroupBuild for memory
    declarations and captures every LayerOutput built inside (chaining any
    outer layer sink), restoring both on exit — including the error path."""
    from paddle_tpu.core.topology import set_layer_sink

    created: Dict[str, LayerOutput] = {}

    def _capture(lo: LayerOutput) -> None:
        created[lo.conf.name] = lo
        if prev_sink is not None:
            prev_sink(lo)

    with _group_build() as gb:
        prev_sink = set_layer_sink(_capture)
        try:
            yield gb, created
        finally:
            set_layer_sink(prev_sink)


def _make_placeholders(gname, scanned, sub_scanned, statics):
    """Scan/static step-input placeholder confs for a group being built."""
    step_args: List[LayerOutput] = []
    scan_placeholders: List[LayerConf] = []
    static_placeholders: List[LayerConf] = []
    for k, lo in enumerate(scanned):
        conf = LayerConf(
            name=f"{gname}@in{k}", type="step_input", size=lo.size, bias=False,
            attrs={"step_seq": sub_scanned[k]},
        )
        scan_placeholders.append(conf)
        step_args.append(LayerOutput(conf))
    for k, st in enumerate(statics):
        conf = LayerConf(
            name=f"{gname}@static{k}",
            type="step_input",
            size=st.input.size,
            bias=False,
            attrs={"static_seq": st.is_seq},
        )
        static_placeholders.append(conf)
        step_args.append(LayerOutput(conf))
    return step_args, scan_placeholders, static_placeholders


def _finalize_group(
    gname, scanned, sub_scanned, statics, scan_placeholders,
    static_placeholders, gb, created, step_outputs, reverse,
) -> LayerOutput:
    """Assemble the recurrent_group LayerConf from a traced step body —
    shared by the step-function form above and the raw
    RecurrentLayerGroupBegin/End config face (v1_compat.raw_face)."""
    unset = [m.name for m in gb.memories if m.attrs["link"] is None]
    if unset:
        raise ValueError(
            f"memories {unset} in recurrent_group {gname!r} have no link: "
            "pass name= or call .set_input(layer) inside the step"
        )
    # Memory link targets must be part of the sub-topology even when not on
    # the path to the step output (reference: a memory may link a layer
    # built purely for the recurrence, e.g. last_seq over the inner rnn in
    # sequence_nest_rnn.conf) — add those as extra sub-topology roots.
    sub_topo = Topology(list(step_outputs))
    link_bases = list(dict.fromkeys(  # order-preserving dedup: deterministic
        m.attrs["link"].split("@")[0] for m in gb.memories
    ))
    extra_roots = [
        created[base]
        for base in link_bases
        if base not in sub_topo.layers and base in created
    ]
    if extra_roots:
        sub_topo = Topology(list(step_outputs) + extra_roots)
    # links may address auxiliary outputs like "<layer>@cell" (lstm_step)
    missing_links = [
        m
        for m in gb.memories
        if m.attrs["link"].split("@")[0] not in sub_topo.layers
    ]
    if missing_links:
        raise ValueError(
            f"memory links {[m.attrs['link'] for m in missing_links]} not found "
            f"in recurrent_group {gname!r} step outputs' graph"
        )

    # Boot layers are OUTER layers: include them as group parents so their
    # values exist in ctx.outputs at apply time.
    outer_inputs: List[LayerOutput] = (
        list(scanned) + [s.input for s in statics] + list(gb.boot_layers.values())
    )

    conf = LayerConf(
        name=gname,
        type="recurrent_group",
        size=step_outputs[0].size,
        inputs=tuple(o.name for o in outer_inputs),
        bias=False,
        attrs={
            "_sub_topology": sub_topo,
            "_memories": tuple(gb.memories),
            "_scan_placeholders": tuple(c.name for c in scan_placeholders),
            "_sub_scanned": tuple(sub_scanned),
            "_static_placeholders": tuple(
                (c.name, c.attrs.get("static_seq", False))
                for c in static_placeholders
            ),
            "_output": step_outputs[0].name,
            "n_scanned": len(scanned),
            "reverse": reverse,
        },
    )
    return LayerOutput(conf, outer_inputs)


# ---------------------------------------------------------------------------
# layer implementation
# ---------------------------------------------------------------------------


def _rg_init(conf, in_confs, rng):
    from paddle_tpu.core.compiler import CompiledNetwork

    sub = CompiledNetwork(conf.attrs["_sub_topology"])
    return sub.init_params(rng)


def _rg_init_state(conf, in_confs):
    from paddle_tpu.core.compiler import CompiledNetwork

    sub = CompiledNetwork(conf.attrs["_sub_topology"])
    return sub.init_state()


@register_layer(
    "recurrent_group", init=_rg_init, init_state=_rg_init_state, auto_activation=False
)
def recurrent_group_apply(conf, params, inputs, ctx: ApplyContext) -> SeqTensor:
    from paddle_tpu.core.compiler import CompiledNetwork

    a = conf.attrs
    sub_topo: Topology = a["_sub_topology"]
    # Inherit the enclosing network's compute dtype so scan carries keep a
    # consistent dtype under mixed precision.
    subnet = CompiledNetwork(sub_topo, compute_dtype=ctx.dtype)
    memories: Sequence[LayerConf] = a["_memories"]
    scan_names: Sequence[str] = a["_scan_placeholders"]
    static_info = a["_static_placeholders"]
    out_name: str = a["_output"]
    n_scan = a["n_scanned"]
    reverse = a["reverse"]

    sub_scanned = a.get("_sub_scanned", (False,) * n_scan)
    scanned = inputs[:n_scan]
    statics = inputs[n_scan : n_scan + len(static_info)]  # rest are boot layers
    lengths = scanned[0].lengths
    assert lengths is not None, "recurrent_group inputs must be sequences"
    t_max = scanned[0].max_len  # outer scan extent: T (plain) or S (nested)
    b = scanned[0].batch_size

    # Outer-axis-major scanned inputs, as SeqTensor pytrees so lax.scan
    # slices data AND per-subsequence lengths together: a nested input
    # [B, S, T, D] + sub_lengths [B, S] scans to an ordinary [B, T, D]
    # sequence per step (the TPU-native hasSubseq path —
    # RecurrentGradientMachine.cpp:446 re-batches frames instead).
    xs = []
    for s_in, is_sub in zip(scanned, sub_scanned):
        if is_sub:
            assert s_in.is_nested, (
                f"{conf.name}: SubsequenceInput requires a nested slot"
            )
            data = jnp.swapaxes(s_in.data, 0, 1)  # [S, B, T, ...]
            sub_len = jnp.swapaxes(s_in.sub_lengths, 0, 1)  # [S, B]
            if reverse:
                data = jnp.flip(data, axis=0)
                sub_len = jnp.flip(sub_len, axis=0)
            xs.append(SeqTensor(data, sub_len))
        else:
            x = jnp.swapaxes(s_in.data, 0, 1)  # [T, B, D]
            if reverse:
                x = jnp.flip(x, axis=0)
            xs.append(SeqTensor(x))
    tpos = jnp.arange(t_max, dtype=jnp.int32)[:, None]  # [T, 1]
    if reverse:
        valid = tpos >= (t_max - lengths[None, :])
    else:
        valid = tpos < lengths[None, :]
    mask_seq = valid[..., None].astype(jnp.float32)  # [T, B, 1]

    static_batch = {
        pname: (st if is_seq else SeqTensor(st.data))
        for (pname, is_seq), st in zip(static_info, statics)
    }
    sub_state0 = ctx.state.get(conf.name, {})

    # Sequence-valued memories (reference sequence-memory frames,
    # RecurrentGradientMachine.cpp:530-608) carry a whole padded sequence:
    # their static width must equal the linked layer's per-step padded
    # width, found by abstract evaluation of the step body (fixed-point
    # iteration: a link whose width depends on the memory's own width — e.g.
    # an elementwise transform — converges in one extra round).
    seq_widths = _seq_memory_widths(
        conf, subnet, params, memories, scan_names, static_batch, xs,
        ctx, sub_state0, b,
    )

    # initial memory carries
    init_carry = {}
    for m in memories:
        boot = m.attrs.get("boot")
        boot_const = m.attrs.get("boot_const_id")
        if m.attrs.get("is_seq"):
            w = seq_widths[m.name]
            if boot is not None:
                bt = ctx.outputs[boot]
                if bt.is_seq:
                    if bt.data.shape[1] > w:
                        # the boot layer's PADDED width exceeds the link's
                        # converged fixed-point width: any boot sequence
                        # longer than w loses its tail here.  Lengths are
                        # traced values, so whether real timesteps (vs mere
                        # padding, e.g. bucketed feeder pads) are dropped
                        # is unknowable at trace time — warn with both
                        # widths instead of clipping silently (the lengths
                        # clamp below keeps ≤w boots exactly correct).
                        warnings.warn(
                            f"seq memory '{m.name}': boot layer '{boot}' is "
                            f"padded to {bt.data.shape[1]} steps but the "
                            f"linked layer's fixed-point width is {w}; boot "
                            f"sequences longer than {w} steps will be "
                            "truncated before the first outer step",
                            stacklevel=2,
                        )
                    d = bt.data[:, :w]
                    if d.shape[1] < w:
                        pad = [(0, 0), (0, w - d.shape[1])] + [(0, 0)] * (
                            d.ndim - 2
                        )
                        d = jnp.pad(d, pad)
                    init_carry[m.name] = SeqTensor(
                        d, jnp.minimum(bt.lengths, w).astype(jnp.int32)
                    )
                else:  # non-seq boot -> a length-1 sequence
                    d = jnp.pad(
                        bt.data[:, None], [(0, 0), (0, w - 1), (0, 0)]
                    )
                    init_carry[m.name] = SeqTensor(
                        d, jnp.ones((b,), jnp.int32)
                    )
            else:  # unbooted: EMPTY sequence (zero lengths), not zeros-as-data
                init_carry[m.name] = SeqTensor(
                    jnp.zeros((b, w, m.size), ctx.dtype),
                    jnp.zeros((b,), jnp.int32),
                )
        elif boot is not None:
            init_carry[m.name] = ctx.outputs[boot].data
        elif boot_const is not None:
            # id-type memory booted with a constant id (reference
            # boot_with_const_id — used for generated-input memories);
            # these DO follow the scanned ids' integer dtype
            init_carry[m.name] = jnp.full(
                (b, m.size), boot_const, scanned[0].data.dtype
            )
        else:
            # memories carry float layer state: zeros at the COMPUTE dtype,
            # never the first scanned input's (an id sequence scanned first
            # made the carry int32 while the linked fc emits floats —
            # sequence_nest_rnn_multi_input.conf)
            init_carry[m.name] = jnp.zeros((b, m.size), ctx.dtype)

    step_rng = ctx.layer_rng(conf.name)
    t_iota = jnp.arange(t_max, dtype=jnp.uint32)

    # Epilogue hoisting: the maximal rowwise SUFFIX of the step graph that
    # no memory depends on runs ONCE on the stacked sequence, its [T, B]
    # folded into T*B rows (_HoistRows: time-major, or shard-major under a
    # data mesh so the rows stay sharded as B was), instead of per scan
    # step.  The canonical win is a per-step vocab projection (seq2seq
    # dec_out: 50 latency-bound [B,512]x[512,30000] GEMMs + a
    # [512,30000] grad accumulator carried through every backward step
    # become one batched GEMM) — the generalization of keeping input
    # projections outside the cell scans, and the TPU analogue of the
    # reference evaluating output frames via SequenceToBatch re-batching.
    # Disabled for nested inputs and sequence-valued memories, whose step
    # outputs are not plain [B, D] rows.
    # both hoists assume plain [B, D] per-step rows and non-seq carries
    rows = _HoistRows(t_max, b, ctx.mesh)
    rows_hoistable = not any(sub_scanned) and not any(
        m.attrs.get("is_seq") for m in memories
    )
    epilogue = None
    frontier = (out_name,)
    if rows_hoistable:
        static_seq = {p for (p, is_seq) in static_info if is_seq}
        epilogue, frontier = _split_epilogue(
            sub_topo, memories, out_name, static_seq
        )
    static_names = {p for (p, _s) in static_info}
    if epilogue is not None:
        # validate by a ONE-step abstract eval (shapes only) that every
        # frontier value really is a plain [B, D] row — a loop layer can
        # emit a sequence (expand over a static, sub-seq transforms) whose
        # stacked form must not be folded into rows
        probe = dict(static_batch)
        for pname, x in zip(scan_names, xs):
            probe[pname] = jax.tree_util.tree_map(lambda v: v[0], x)
        for m in memories:
            # mirror the real carries (dtype matters: id memories are int)
            probe[m.name] = SeqTensor(init_carry[m.name])
        outs_shape = jax.eval_shape(
            lambda p, pb: subnet.apply(
                p, pb, state=sub_state0, train=ctx.train, rng=None,
                only=set(sub_topo.order) - epilogue,
            )[0],
            params,
            probe,
        )
        scan_name_set = set(scan_names)
        for n in frontier:
            if n in static_names or n in scan_name_set:
                continue  # preset straight from the outer values below
            st = outs_shape[n]
            if (
                st.lengths is not None
                or st.sub_lengths is not None
                or st.data.ndim != 2
            ):
                epilogue, frontier = None, (out_name,)
                break
    # Prologue hoisting (the prefix complement): rowwise layers fed only by
    # scanned/static placeholders — in-step input projections like
    # gru_unit/lstmemory_group's mixed 3H/4H GEMMs — compute once on the
    # inputs folded into rows (_HoistRows); the body reads their per-step
    # slices.
    _pro_producer = _producer_resolver(sub_topo.layers)
    prologue = set()
    if rows_hoistable:
        prologue = _split_prologue(
            sub_topo, scan_names, static_info, epilogue or set()
        )
    pro_outs = {}
    pro_sliced = ()
    if prologue:
        pre_preset = {}
        for pname, x in zip(scan_names, xs):
            d = x.data  # [T, B, ...] (already flipped for reverse groups)
            pre_preset[pname] = SeqTensor(rows.fold(d))
        for (pname, is_seq) in static_info:
            if not is_seq:
                pre_preset[pname] = SeqTensor(
                    rows.tile(static_batch[pname].data)
                )
        pro_outs, _ = subnet.apply(
            params, {}, state=sub_state0, train=ctx.train, rng=None,
            only=prologue, preset=pre_preset,
        )
        # every computed output (incl. "@side" keys) whose base layer was
        # hoisted becomes a per-step scan input for the body
        pro_sliced = tuple(
            n for n in pro_outs if _pro_producer(n) in prologue
        )

    body_only = set(sub_topo.order) - (epilogue or set()) - prologue
    loop_only = (
        None if epilogue is None and not prologue else body_only
    )
    # static frontier inputs are step-invariant (tiled into the epilogue
    # preset directly); prologue-produced frontier values are already
    # available as rows — the scan emits neither
    frontier_scan = tuple(
        n for n in frontier
        if epilogue is None
        or (
            n not in static_names
            and n not in scan_names
            and _pro_producer(n) not in prologue
        )
    )
    pro_stacked = tuple(rows.unfold(pro_outs[n].data) for n in pro_sliced)

    # Fused attention-GRU lowering: when the whole remaining loop body IS
    # the v1 attention-decoder idiom (layers/attention.py
    # match_attention_gru_step), replace the generic per-layer scan with
    # the fused custom-VJP core (ops/rnn.py _attgru_core) — state
    # projection + GRU gates share one GEMM per step, the target-side
    # input projection runs once on the whole sequence, and the backward
    # defers every weight gradient to post-scan einsums.  v1 configs hit
    # this with no edits; any structural mismatch falls through to the
    # generic scan below.
    fused_hs = None
    from paddle_tpu.utils.flags import get_flag

    if (
        rows_hoistable
        and len(memories) == 1
        and not sub_state0
        and get_flag("fused_attention_gru")
    ):
        fused_hs = _try_fused_attention_gru(
            conf, subnet, params, memories[0], scan_names, static_info,
            static_batch, scanned, xs, mask_seq, init_carry, ctx,
            set(body_only), frontier_scan,
        )

    def body_core(carry_all, scan_in):
        carry, sub_state = carry_all
        n_x = len(xs)
        xt = scan_in[:n_x]
        pro_t = scan_in[n_x:-2]
        m_t = scan_in[-2]
        t_idx = scan_in[-1]
        sub_batch = dict(static_batch)
        for pname, x in zip(scan_names, xt):
            sub_batch[pname] = x  # SeqTensor: a sequence when SubsequenceInput
        for m in memories:
            if m.attrs.get("is_seq"):
                sub_batch[m.name] = carry[m.name]  # whole-sequence SeqTensor
            else:
                sub_batch[m.name] = SeqTensor(carry[m.name])
        # fold the timestep in so dropout/sampling decorrelate across steps
        rng_t = None if step_rng is None else jax.random.fold_in(step_rng, t_idx)
        outs, new_sub_state = subnet.apply(
            params, sub_batch, state=sub_state, train=ctx.train, rng=rng_t,
            only=loop_only,
            preset={
                n: SeqTensor(p) for n, p in zip(pro_sliced, pro_t)
            } or None,
        )
        new_carry = {}
        for m in memories:
            upd = outs[m.attrs["link"]]
            if m.attrs.get("is_seq"):
                old = carry[m.name]
                assert upd.lengths is not None, (
                    f"{conf.name}: seq memory {m.name} links "
                    f"{m.attrs['link']!r}, which is not a sequence"
                )
                new_carry[m.name] = SeqTensor(
                    jnp.where(
                        m_t[..., None] > 0,
                        upd.data,
                        old.data.astype(upd.data.dtype),
                    ),
                    jnp.where(m_t[:, 0] > 0, upd.lengths, old.lengths),
                )
            else:
                new_carry[m.name] = jnp.where(
                    m_t > 0, upd.data, carry[m.name].astype(upd.data.dtype)
                )
        # Return the whole SeqTensor so a seq-valued step output stacks its
        # per-step lengths too (the nested-output case).
        return (new_carry, new_sub_state), tuple(
            outs[n] for n in frontier_scan
        )

    # Mask-aware scan early-exit: when a batch's true max length sits below
    # the padded ladder rung (the bucket-shape contract pads T up to 16·2^k
    # — core.batch.canonicalize_batch / DataFeeder(ladder=...)), the
    # trailing scan steps are pure padding for EVERY row.  Wrapping the body
    # in lax.cond on a per-step any-row-live bit turns those dead steps into
    # a carry pass-through: the compiled shape stays the rung's (one
    # executable per bucket), the executed trip count shrinks to the bucket
    # bound.  Reverse groups flip their inputs, so their dead steps sit at
    # the START of the scan — the per-step bit covers both ends.
    scan_xs = tuple(xs) + pro_stacked + (mask_seq, t_iota)
    body = body_core
    if fused_hs is None and get_flag("scan_early_exit"):
        active_seq = jnp.any(valid, axis=1)  # [T] any row live at step t
        # dead steps must emit the live branch's exact output structure;
        # abstract-eval the body once (shapes only, no FLOPs) to know it
        slice0 = jax.tree_util.tree_map(lambda v: v[0], scan_xs)
        ys_struct = jax.eval_shape(
            lambda c, s: body_core(c, s)[1], (init_carry, sub_state0), slice0
        )

        def body(carry_all, scan_in):
            def live(c):
                return body_core(c, scan_in[:-1])

            def dead(c):
                zeros = jax.tree_util.tree_map(
                    lambda st: jnp.zeros(st.shape, st.dtype), ys_struct
                )
                return c, zeros

            return jax.lax.cond(scan_in[-1], live, dead, carry_all)

        scan_xs = scan_xs + (active_seq,)

    # Memory/step placeholders ride the compiler's data path per step.
    if fused_hs is not None:
        ys_stacked = (SeqTensor(fused_hs),)
    else:
        (_, sub_state_out), ys_stacked = jax.lax.scan(  # num: allow[N401] generic-group backward: weight cotangents accumulate at compute dtype across <=T ladder steps (PR-2 parity contract); f32 master updates + the bf16 convergence tests gate the loss
            body,
            (init_carry, sub_state0),
            scan_xs,
            unroll=_GROUP_UNROLL,
        )
        if sub_state0:
            ctx.new_state[conf.name] = sub_state_out

    if epilogue is not None:
        # run the hoisted suffix once over the whole stacked sequence, time
        # folded into the batch in _HoistRows' order (rowwise layers only,
        # so the [T*B] rows are independent)
        preset = {}
        for n, st in zip(frontier_scan, ys_stacked):
            preset[n] = SeqTensor(rows.fold(st.data))
        for n in frontier:
            if n in preset:
                continue
            if _pro_producer(n) in prologue:
                preset[n] = pro_outs[n]  # already rows, in the same order
            elif n in scan_names:
                # the scan input itself: already held time-major in xs
                preset[n] = SeqTensor(
                    rows.fold(xs[scan_names.index(n)].data)
                )
            else:  # step-invariant static: broadcast per step, don't stack
                preset[n] = SeqTensor(rows.tile(static_batch[n].data))
        epi_outs, _ = subnet.apply(
            params, {}, state=sub_state0, train=ctx.train, rng=None,
            only=epilogue, preset=preset,
        )
        ys = rows.unfold_batch_major(epi_outs[out_name].data, reverse)
        lg = epi_outs.get(out_name + "@logits")
        if lg is not None:
            # expose the hoisted softmax's pre-activation at the GROUP level
            # so a downstream cross_entropy fuses into log-softmax CE and
            # the [B, T, vocab] probabilities dead-code-eliminate entirely
            ctx.outputs[conf.name + "@logits"] = SeqTensor(
                rows.unfold_batch_major(lg.data, reverse), lengths
            )
            # ... and the same values as the ROWS they were computed as, for
            # a consumer that reduces them over the vocabulary (softmax-CE,
            # the evaluator's argmax): it folds its narrow per-token input
            # into the rows' order and unfolds its narrow per-row result,
            # and the [B, T, vocab] view above stays unread.  Read, that
            # view costs a copy of the whole array: XLA:TPU lays the GEMM's
            # [T*B, V] output out with the rows along the lanes, which no
            # [B, T] split of the rows is a bitcast of (PERF.md, PR 37).
            ctx.outputs[conf.name + "@logits_rows"] = HoistedRows(
                lg.data, rows, reverse
            )
    else:
        ys = ys_stacked[0]
        if ys.lengths is not None:
            # step emitted sequences -> nested [B, S, T, ...] output
            data, sub_len = ys.data, ys.lengths
            if reverse:
                data = jnp.flip(data, axis=0)
                sub_len = jnp.flip(sub_len, axis=0)
            data = jnp.swapaxes(data, 0, 1)  # [B, S, T, ...]
            out = SeqTensor(data, lengths, jnp.swapaxes(sub_len, 0, 1))
            return out.with_data(out.masked_data())
        ys = ys.data
        if reverse:
            ys = jnp.flip(ys, axis=0)
        ys = jnp.swapaxes(ys, 0, 1)  # [B, T, D]
    ys = ys * mask_like(ys, lengths)
    return SeqTensor(ys, lengths)


def _try_fused_attention_gru(
    conf, subnet, params, mem, scan_names, static_info, static_batch,
    scanned, xs, mask_seq, init_carry, ctx, body_only, frontier_scan,
):
    """Lower a matched attention-GRU decoder step onto ops/rnn._attgru_core.

    Returns the [T, B, H] hidden sequence (time-major, matching what the
    generic scan would emit for the gru frontier value), or None when the
    step doesn't match / a runtime precondition fails — the caller then
    runs the generic scan.  Numerics are pinned identical to the unfused
    lowering by tests/test_attention_gru_fused.py."""
    from paddle_tpu.core.compiler import _cast_floats
    from paddle_tpu.layers.attention import match_attention_gru_step
    from paddle_tpu.ops.rnn import _attgru_core
    from paddle_tpu.utils.flags import get_flag

    sub_topo: Topology = conf.attrs["_sub_topology"]
    static_seq = {p for (p, is_seq) in static_info if is_seq}
    match = match_attention_gru_step(
        sub_topo.layers, mem, set(scan_names), static_seq
    )
    if match is None:
        return None
    # the fused core must replace the loop body EXACTLY: the scan's only
    # emitted value is the gru state, and every loop-resident layer is part
    # of the matched pattern (no extra step outputs, no side computation)
    if tuple(frontier_scan) != (match.gru,):
        return None
    loop_layers = {
        n for n in body_only
        if sub_topo.layers[n].type not in ("data", "step_input", "memory")
    }
    if loop_layers != set(match.matched):
        return None
    # runtime preconditions on the actual tensors
    enc_t = static_batch[match.enc_name]
    ep_t = static_batch[match.ep_name]
    if enc_t.data.ndim != 3 or ep_t.data.ndim != 3:
        return None
    # the unfused path masks the score softmax by enc_proj's lengths and
    # the context sum by enc's — only equivalent to the core's single mask
    # when they are the same lengths array (they are: enc_proj is a rowwise
    # projection of enc, which propagates the identical lengths object)
    if enc_t.lengths is not ep_t.lengths and not (
        enc_t.lengths is None and ep_t.lengths is None
    ):
        return None
    scan_idx = {n: i for i, n in enumerate(scan_names)}
    for _slot, pname in match.scan_slots:
        x = xs[scan_idx[pname]]
        s_in = scanned[scan_idx[pname]]
        if (
            x.lengths is not None  # SubsequenceInput slice: not a plain row
            or x.data.ndim != 3
            or getattr(s_in, "sparse_ids", False)
            or not jnp.issubdtype(x.data.dtype, jnp.floating)
        ):
            return None

    mixed = ctx.dtype != jnp.dtype(jnp.float32)

    def layer_p(name):
        p = subnet.layer_params(params, name)
        return _cast_floats(p, ctx.dtype) if mixed else p

    p_sp = layer_p(match.state_proj)
    p_sc = layer_p(match.scores)
    p_in = layer_p(match.in_proj)
    p_gru = layer_p(match.gru)
    if "w_h" not in p_gru or "w_c" not in p_gru:
        return None

    # fused state weight: one [H, P+2H] GEMM covers the attention state
    # projection AND the GRU update/reset gates
    w1 = jnp.concatenate([p_sp["w0"], p_gru["w_h"]], axis=1)
    v = p_sc["w0"][:, 0]
    w_ctx = p_in[f"w{match.ctx_slot}"]
    w_c = p_gru["w_c"]

    # target-side gate projections for the WHOLE sequence, outside the scan
    # (the generic path re-ran this [B,*]x[*,3H] GEMM every step because it
    # shares an fc with the in-loop context term)
    xg = None
    for slot, pname in match.scan_slots:
        x = xs[scan_idx[pname]].data  # [T, B, D], already flipped if reverse
        term = acc_einsum("tbd,dg->tbg", x, p_in[f"w{slot}"])
        xg = term if xg is None else xg + term
    for p in (p_in, p_gru):
        if "b" in p:
            xg = xg + p["b"]  # num: allow[N401] gate-bias grad sums over T at compute dtype; every weight grad in the fused core accumulates f32 post-scan
    ep = ep_t.data
    if "b" in p_sp:
        ep = ep + p_sp["b"]  # state-proj bias is step-invariant: fold here

    emask = enc_t.mask(bool) if enc_t.lengths is not None else None
    hs, _h_last = _attgru_core(
        (match.gate_act, match.act, match.att_act,
         bool(get_flag("scan_early_exit"))),
        xg, enc_t.data, ep, emask, w1, v, w_ctx, w_c,
        init_carry[mem.name], mask_seq > 0,
    )
    return hs


# Layer types whose rows are independent (time can fold into batch): every
# mixed projection kind is per-row (full_matrix/trans_full_matrix/table/
# identity/identity_offset/slice/scaling/dotmul — layers/mixed.py), and
# conv/context projections enter mixed as identity terms of ordinary
# layers, which would simply not hoist.
_HOIST_ROWWISE = frozenset(
    {"fc", "addto", "slope_intercept", "mixed", "embedding"}
)


def _producer_resolver(layers):
    """Map an input reference to its producing layer name: raw names pass
    through; "layer@side" side-output keys (lstm_step's "unit@cell")
    resolve to the base layer — but ONLY when the base actually names a
    layer, because scan/static placeholders legitimately contain '@'
    ("group@in0") and must not be mangled."""

    def producer(i):
        if i in layers:
            return i
        b = i.split("@")[0]
        return b if b in layers else i

    return producer


def _hoist_eligible(c, impl):
    return (
        c.type in _HOIST_ROWWISE
        and c.drop_rate == 0.0
        and impl.init_state is None
        and c.act != "sequence_softmax"
        and not c.attr("error_clip", 0.0)
    )


def _split_prologue(sub_topo, scan_names, static_info, epilogue):
    """The PREFIX complement of epilogue hoisting: rowwise layers whose
    transitive inputs are only scanned/static placeholders (never a
    memory) compute identically at every scan step offset — the classic
    in-step input projection (gru_unit/lstmemory_group's mixed 3H/4H
    projections; reference SequenceToBatch feeds pre-projected frames).
    They run ONCE before the scan on the inputs folded into T*B rows
    (_HoistRows gives the row order); the body receives their per-step
    slices as extra scan inputs.  Returns the set of hoisted names (possibly
    empty)."""
    from paddle_tpu.layers.base import get_layer_impl

    layers = sub_topo.layers
    producer = _producer_resolver(layers)
    scanned = set(scan_names)
    static_ok = {p for (p, is_seq) in static_info if not is_seq}
    prologue = set()
    for name in sub_topo.order:
        c = layers[name]
        if c.type in ("data", "step_input", "memory") or name in epilogue:
            continue
        if not _hoist_eligible(c, get_layer_impl(c.type)):
            continue
        deps = [producer(i) for i in c.inputs]
        if not all(
            d in scanned or d in static_ok or d in prologue for d in deps
        ):
            continue
        if not any(d in scanned or d in prologue for d in deps):
            continue  # step-invariant (static-only): nothing to batch over
        prologue.add(name)
    return prologue


def _split_epilogue(sub_topo, memories, out_name, static_seq):
    """Partition the step graph for epilogue hoisting.

    Returns (epilogue_names, frontier_names): `epilogue` is the maximal
    suffix reaching `out_name` whose layers are rowwise (independent per
    [B] row, so time can fold into batch, in _HoistRows' order), stateless,
    dropout-free, and not ancestors of any memory link; `frontier` is every
    non-epilogue name the epilogue reads (loop layers, memory/step
    placeholders) — the scan body emits exactly these.  (None, (out_name,))
    when nothing hoists."""
    from paddle_tpu.layers.base import get_layer_impl

    layers = sub_topo.layers
    producer = _producer_resolver(layers)
    loop_needed = set()
    stack = [producer(m.attrs["link"]) for m in memories]
    while stack:
        n = stack.pop()
        if n in loop_needed:
            continue
        loop_needed.add(n)
        if n in layers:  # memory placeholders live outside the sub topology
            stack.extend(producer(i) for i in layers[n].inputs)

    consumers: Dict[str, set] = {}
    for n in sub_topo.order:
        for i in layers[n].inputs:
            consumers.setdefault(producer(i), set()).add(n)

    epilogue = set()
    for name in reversed(sub_topo.order):
        cons = consumers.get(name, set())
        wanted = name == out_name or bool(cons)
        if not wanted or name in loop_needed:
            continue
        if not all(c in epilogue for c in cons):
            # SOME consumer stays in the loop (or is off the out cone), so
            # this output must be computed there; hoisting it too would
            # leave the loop-resident consumer reading a value the scan
            # body never produced (diamond graphs)
            continue
        c = layers[name]
        if c.type in ("data", "step_input", "memory"):
            continue  # placeholder: becomes frontier
        if not _hoist_eligible(c, get_layer_impl(c.type)):
            # ineligible: stays in the loop; consumers already in the
            # epilogue read it from the frontier
            loop_needed.add(name)
            continue
        epilogue.add(name)
    if out_name not in epilogue:
        return None, (out_name,)
    order_ix = {n: i for i, n in enumerate(sub_topo.order)}
    frontier = []
    for e in sorted(epilogue, key=order_ix.__getitem__):
        for i in layers[e].inputs:
            if producer(i) not in epilogue and i not in frontier:
                if i in static_seq:
                    # a sequence-valued static feeding the suffix: its
                    # per-step value is not a plain [B, D] row — bail
                    return None, (out_name,)
                frontier.append(i)
    return epilogue, tuple(frontier)


def _seq_memory_widths(
    conf, subnet, params, memories, scan_names, static_batch, xs,
    ctx, sub_state0, b,
) -> Dict[str, int]:
    """Static padded width of each sequence-valued memory = the linked
    layer's per-step padded width, found by abstract evaluation
    (jax.eval_shape) of the step body — no FLOPs, shapes only.  Iterates to
    a fixed point because a link's width can depend on the memory's own
    width (elementwise transforms of the memory); widths that keep changing
    (e.g. a concat that grows every step) cannot be a static scan carry and
    raise."""
    seq_mems = [m for m in memories if m.attrs.get("is_seq")]
    if not seq_mems:
        return {}
    # first-step slices of the scanned inputs, exactly as lax.scan hands
    # them to the body ([T,B,...] -> [B,...], nested sub-lengths included)
    x0 = [jax.tree_util.tree_map(lambda v: v[0], x) for x in xs]

    # initial guess: boot width, else the inner width of a nested scanned
    # input (the usual link target in hierarchical steps — a bad guess can
    # make the probe fail outright, e.g. addto(memory, subsequence) with
    # mismatched widths, before the fixed point is ever reached)
    nested_w = next(
        (x.data.shape[1] for x in x0 if getattr(x, "lengths", None) is not None),
        1,
    )
    widths: Dict[str, int] = {}
    for m in seq_mems:
        boot = m.attrs.get("boot")
        if boot is not None and ctx.outputs[boot].is_seq:
            widths[m.name] = ctx.outputs[boot].max_len
        else:
            widths[m.name] = nested_w

    def run_shapes(pb):
        return jax.eval_shape(
            lambda p, bb: subnet.apply(
                p, bb, state=sub_state0, train=ctx.train, rng=None
            )[0],
            params,
            pb,
        )

    for _ in range(3):
        pb = dict(static_batch)
        for pname, x in zip(scan_names, x0):
            pb[pname] = x
        for m in memories:
            if m.attrs.get("is_seq"):
                pb[m.name] = SeqTensor(
                    jnp.zeros((b, widths[m.name], m.size), jnp.float32),
                    jnp.zeros((b,), jnp.int32),
                )
            else:
                pb[m.name] = SeqTensor(jnp.zeros((b, m.size), jnp.float32))
        outs = run_shapes(pb)
        new_widths: Dict[str, int] = {}
        stable = True
        for m in seq_mems:
            out = outs[m.attrs["link"]]
            if out.lengths is None:
                raise ValueError(
                    f"{conf.name}: memory(is_seq=True) {m.name} links "
                    f"{m.attrs['link']!r}, which is not a sequence layer"
                )
            new_widths[m.name] = out.data.shape[1]
            stable = stable and new_widths[m.name] == widths[m.name]
        if stable:
            return widths
        widths = new_widths
    raise ValueError(
        f"{conf.name}: sequence-memory padded width did not reach a fixed "
        f"point (last {widths}); a step whose linked sequence grows every "
        "iteration cannot be carried through a static-shape scan"
    )


class _HoistRows:
    """Row order of the hoisted prologue/epilogue: how a stacked [T, B, ...]
    value folds into the [T*B, ...] rows the rowwise layers run on, and back.

    Without a data mesh the rows are time-major (row t*B + b): a reshape.
    Under a mesh the batch axis is split over the ``data`` axis in n blocks
    of B/n rows, and a time-major merge interleaves the chips' rows T times:
    no sharding of the merged axis says "every B/n-th block of each
    T-slice", so XLA's partitioner gathers the whole batch onto every chip
    first and each chip runs the hoisted layers on all B rows.  Where n > 1
    divides B the rows are therefore shard-major, time-major inside a shard
    (row (s*T + t)*B/n + b'): a chip's rows are one contiguous block, the
    merged axis is sharded as B was, and each chip runs the one-chip program
    on its own B/n rows.  n = 1 (no mesh, a data axis of one, or a B that n
    does not divide) is exactly the time-major reshape."""

    def __init__(self, t: int, b: int, mesh) -> None:
        n = 1 if mesh is None else mesh.shape.get(DATA_AXIS, 1)
        self.t, self.b = t, b
        self.n = n if b % n == 0 else 1

    # static part of a HoistedRows pytree: two traces of one shape agree
    def __eq__(self, other) -> bool:
        return type(other) is type(self) and (
            (self.t, self.b, self.n) == (other.t, other.b, other.n)
        )

    def __hash__(self) -> int:
        return hash((self.t, self.b, self.n))

    def fold(self, d: jnp.ndarray) -> jnp.ndarray:
        """Stacked [T, B, ...] -> rows [T*B, ...]."""
        t, b, n = self.t, self.b, self.n
        rest = d.shape[2:]
        if n > 1:
            d = jnp.swapaxes(d.reshape((t, n, b // n) + rest), 0, 1)
        return d.reshape((t * b,) + rest)

    def tile(self, d: jnp.ndarray) -> jnp.ndarray:
        """Step-invariant [B, ...] -> rows [T*B, ...], each step's copy of a
        row where fold puts that step.  broadcast_to + reshape instead of
        jnp.tile: XLA keeps the T-fold expansion a broadcast fused into the
        consumer rather than a materialized copy (a wide static, e.g. an
        encoder summary feeding the hoisted suffix, would otherwise cost T
        times its footprint in HBM)."""
        t, b, n = self.t, self.b, self.n
        rest = d.shape[1:]
        if n > 1:
            d = jnp.broadcast_to(
                d.reshape((n, 1, b // n) + rest), (n, t, b // n) + rest
            )
        else:
            d = jnp.broadcast_to(d[None], (t, b) + rest)
        return d.reshape((t * b,) + rest)

    def unfold(self, r: jnp.ndarray) -> jnp.ndarray:
        """Rows [T*B, ...] -> stacked [T, B, ...] (fold's inverse), the form
        the scan slices per step."""
        t, b, n = self.t, self.b, self.n
        rest = r.shape[1:]
        if n > 1:
            r = jnp.swapaxes(r.reshape((n, t, b // n) + rest), 0, 1)
        return r.reshape((t, b) + rest)

    def unfold_batch_major(self, r: jnp.ndarray, reverse: bool) -> jnp.ndarray:
        """Rows [T*B, ...] -> the group's [B, T, ...] output, time put back
        in order for a reverse group.  Under a mesh this is ONE transpose
        inside each shard, never unfold() and a second swap: with the two
        chained XLA:TPU hoisted the cross-entropy's float32 upcast of the
        logits into the GEMM and laid a float32 copy of them out as well
        (benchmark/aot.py nmt-train-dp4: 9.15 GiB of temporaries against
        4.38)."""
        t, b, n = self.t, self.b, self.n
        if n == 1:
            r = self.unfold(r)
            if reverse:
                r = jnp.flip(r, axis=0)
            return jnp.swapaxes(r, 0, 1)
        rest = r.shape[1:]
        r = r.reshape((n, t, b // n) + rest)
        if reverse:
            r = jnp.flip(r, axis=1)
        return jnp.swapaxes(r, 1, 2).reshape((b, t) + rest)

    def fold_batch_major(self, d: jnp.ndarray, reverse: bool) -> jnp.ndarray:
        """A [B, T, ...] value in the group's output order -> rows
        [T*B, ...] (unfold_batch_major's inverse): how a per-token input of
        a consumer of the rows, the label ids of a cost, gets their order."""
        t, b, n = self.t, self.b, self.n
        if n == 1:
            d = jnp.swapaxes(d, 0, 1)
            if reverse:
                d = jnp.flip(d, axis=0)
            return self.fold(d)
        rest = d.shape[2:]
        d = jnp.swapaxes(d.reshape((n, b // n, t) + rest), 1, 2)
        if reverse:
            d = jnp.flip(d, axis=1)
        return d.reshape((t * b,) + rest)


@jax.tree_util.register_pytree_node_class
class HoistedRows:
    """A hoisted epilogue's output as the [T*B, ...] rows it was computed as,
    with their order (``<group>@logits_rows`` beside ``<group>@logits``).

    ``rows`` is the one leaf; the order (a _HoistRows and the group's
    ``reverse``) is static.  ``fold`` puts a [B, T, ...] per-token value
    into the rows' order, ``unfold`` a per-row result [T*B, ...] back into
    [B, T, ...]: both move one narrow value a row, where reading the rows
    as [B, T, V] moves all of them."""

    def __init__(self, rows: jnp.ndarray, order: "_HoistRows", reverse: bool):
        self.rows, self.order, self.reverse = rows, order, bool(reverse)

    def tree_flatten(self):
        return (self.rows,), (self.order, self.reverse)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    def fold(self, d: jnp.ndarray) -> jnp.ndarray:
        return self.order.fold_batch_major(d, self.reverse)

    def unfold(self, r: jnp.ndarray) -> jnp.ndarray:
        return self.order.unfold_batch_major(r, self.reverse)


def mask_like(ys: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """[B, T] validity mask broadcast-shaped to ys's rank (ys may carry any
    number of trailing axes — features, beam × token for an in-group
    generator, ...)."""
    t = jnp.arange(ys.shape[1], dtype=jnp.int32)
    m = (t[None, :] < lengths[:, None]).astype(ys.dtype)
    return m.reshape(m.shape + (1,) * (ys.ndim - 2))
