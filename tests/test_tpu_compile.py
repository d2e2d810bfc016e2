"""The blocked attention kernels compiled for the chip WITHOUT the chip: the
TPU's compiler is installed here and compiles for a v5e that is described,
not attached.  Mosaic refuses here what it would refuse there (a slice off
the tiling, a layout it cannot relayout, more VMEM than a kernel may use),
which interpret mode on the CPU cannot show.  Nothing runs: no result, no
time.  Every test that describes the topology lives in THIS file and does so
inside a fixture: each xdist worker imports every test file, and the TPU's
library is loaded by the one that runs this one.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_attention as fa

# nothing is attached, so a second load of the library beside a job that
# holds it is harmless: without the last entry it is refused
_ENV = {"TPU_SKIP_MDS_QUERY": "1", "TPU_ACCELERATOR_TYPE": "v5litepod-4",
        "TPU_WORKER_HOSTNAMES": "localhost", "TPU_LOG_DIR": "disabled",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


@pytest.fixture(scope="module")
def v5e():
    """The four chips of a v5e 2x2 host, described.  Skips where the TPU's
    library is not installed; any other failure to describe them fails."""
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no v5e:2x2 topology can be described here")
    before = {name: os.environ.get(name) for name in _ENV}
    os.environ.update(_ENV)
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    finally:
        for name, value in before.items():
            if value is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = value


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Such a compile is written to the persistent cache and cannot be read
    back: the cache is off for the length of one test of this file."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


# (B, T, H, dh, causal): the Transformer cell's two kinds of layer, the hybrid
# decoder's (after its key/value heads are repeated), a long row
_SHAPES = {
    "transformer-1k": (8, 1024, 8, 64, False),
    "transformer-1k-causal": (8, 1024, 8, 64, True),
    "hybrid-2k-causal": (2, 2048, 32, 128, True),
    "long-8k": (1, 8192, 8, 64, False),
}


@pytest.mark.parametrize("name", sorted(_SHAPES))
def test_the_blocked_kernels_compile_for_a_v5e_at_the_cells_shapes(v5e, name):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(v5e.devices[0])
    b, t, h, dh, causal = _SHAPES[name]
    bq, bk = fa.auto_blocks(t, causal)
    spec = jax.ShapeDtypeStruct((b, t, h, dh), jnp.bfloat16, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)

    def forward_and_backward(q, k, v, g, lengths):
        out, vjp = jax.vjp(lambda *a: fa.flash_attention_diff(*a, lengths, causal, bq, bk, False), q, k, v)
        return (out, *vjp(g))

    compiled = (jax.jit(forward_and_backward).trace(spec, spec, spec, spec, lengths)
                .lower(lowering_platforms=("tpu",)).compile())
    # forward + the one fused backward
    assert compiled.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("quantized", [False, True], ids=["jit-over-the-mesh", "quantized-allreduce"])
def test_a_data_parallel_step_with_the_blocked_kernels_compiles_for_four_chips(v5e, monkeypatch, quantized):
    """trainer/step.py's data-parallel step is ONE program over the mesh, and
    XLA partitions no Mosaic kernel: jax refuses one that is lowered bare into
    such a program.  The layer sees the mesh (ctx.mesh) and puts its kernels
    under a shard_map over the rows: a Transformer of 1,024 keys, one layer of
    each kind, a row a chip, lowers and compiles with all six kernels in it.
    The quantized-allreduce step traces the layers inside a shard_map of its
    own over the whole mesh, where the kernels are called bare."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.models.transformer import transformer_cost
    from paddle_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    from paddle_tpu.trainer.step import make_train_step
    from paddle_tpu.utils.timers import global_stats

    mesh = Mesh(np.array(v5e.devices).reshape(4, 1), (DATA_AXIS, MODEL_AXIS))
    reset_auto_names()
    cost, _ = transformer_cost(50, 50, d_model=128, n_heads=2, n_layers=1, d_ff=64)
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    net.mesh = mesh  # as trainer.SGD sets it
    opt = paddle.optimizer.Adam(learning_rate=1e-3)
    params, state = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    ids = SeqTensor(jax.ShapeDtypeStruct((4, 1024), jnp.int32), jax.ShapeDtypeStruct((4,), jnp.int32))
    batch = {name: ids for name in ("src_word", "trg_word", "trg_next")}
    args = (params, state, jax.eval_shape(opt.init, params), batch, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    by_row = NamedSharding(mesh, P(DATA_AXIS))
    placed = [jax.tree_util.tree_map(
        lambda x, sh=(by_row if arg is batch else NamedSharding(mesh, P())): jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sh), arg) for arg in args]

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    count = lambda: [global_stats.count(f"attention_{path}_layers") for path in ("blocked", "dense")]
    before = count()
    compiled = make_train_step(net, opt, mesh=mesh, quantized=quantized).trace(*placed).lower(lowering_platforms=("tpu",)).compile()
    assert [a - b for a, b in zip(count(), before)] == [3, 0]
    assert compiled.as_text().count("tpu_custom_call") == 6


def test_a_data_parallel_step_with_the_looped_decoder_compiles_for_four_chips(v5e, monkeypatch):
    """`layer_loop` hands its sub-network the trainer's mesh, so the attention
    layers inside the scan put their kernels under a shard_map over the rows
    as they do outside one: a looped decoder of 1,024 keys and heads of 128,
    one layer, two passes, a row a chip, lowers and compiles with one forward
    kernel in the forward scan's body and, in the backward scan's, the
    recomputed forward and the fused backward."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.models.looped_lm import looped_lm_cost
    from paddle_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    from paddle_tpu.trainer.step import make_train_step
    from paddle_tpu.utils.timers import global_stats

    mesh = Mesh(np.array(v5e.devices).reshape(4, 1), (DATA_AXIS, MODEL_AXIS))
    reset_auto_names()
    cost, _ = looped_lm_cost(64, 128, n_layers=1, n_passes=2, n_heads=2, head_dim=128, intermediate=64,
                             exit_beta=0.05)
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    net.mesh = mesh  # as trainer.SGD sets it
    opt = paddle.optimizer.Adam(learning_rate=1e-3)
    params, state = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    ids = SeqTensor(jax.ShapeDtypeStruct((4, 1024), jnp.int32), jax.ShapeDtypeStruct((4,), jnp.int32))
    batch = {"word": ids, "next_word": ids}
    args = (params, state, jax.eval_shape(opt.init, params), batch, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    by_row = NamedSharding(mesh, P(DATA_AXIS))
    placed = [jax.tree_util.tree_map(
        lambda x, sh=(by_row if arg is batch else NamedSharding(mesh, P())): jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sh), arg) for arg in args]

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    count = lambda: [global_stats.count(k) for k in ("attention_blocked_layers", "attention_dense_layers", "loop_passes")]
    before = count()
    compiled = make_train_step(net, opt, mesh=mesh).trace(*placed).lower(lowering_platforms=("tpu",)).compile()
    assert [a - b for a, b in zip(count(), before)] == [1, 0, 2]  # one pass traced, two run
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 and " all-reduce" in text


@pytest.mark.parametrize("rows", ["exposed", "withheld"])
def test_the_seq2seq_step_compiled_for_a_v5e_copies_its_logits_only_for_a_batch_major_reader(v5e, monkeypatch, rows):
    """XLA:TPU lays the hoisted output layer's [T*B, V] product out with the
    rows along the lanes; a reader of its [B, T, V] view costs a `copy` of the
    whole array into another tiling (`nmt-train`: 1.72 GB, 5.7 ms a step,
    PERF.md PR 37).  With softmax-CE and the evaluator's argmax on the rows
    (`<group>@logits_rows`) the compiled training step holds no copy of an
    array of T*B x V elements; with the rows withheld it holds them, at these
    widths too (T = 56 as the cell pads to, so that no [B, T] split of the
    rows tiles the sublanes)."""
    import importlib

    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.models.seq2seq import seq2seq_cost
    from paddle_tpu.trainer.evaluators import default_metrics_fn
    from paddle_tpu.trainer.step import make_train_step
    from paddle_tpu.utils.timers import global_stats

    if rows == "withheld":
        rg = importlib.import_module("paddle_tpu.layers.recurrent_group")
        monkeypatch.setattr(rg, "HoistedRows", lambda *a: None)
    vocab, b, t = 1000, 128, 56
    one_chip = SingleDeviceSharding(v5e.devices[0])
    reset_auto_names()
    cost, _ = seq2seq_cost(vocab, vocab, word_dim=64, hidden_dim=64)
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    opt = paddle.optimizer.Adam(learning_rate=1e-3)
    params, state = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    ids = SeqTensor(jax.ShapeDtypeStruct((b, t), jnp.int32), jax.ShapeDtypeStruct((b,), jnp.int32))
    batch = {name: ids for name in ("src_word", "trg_word", "trg_next")}
    args = (params, state, jax.eval_shape(opt.init, params), batch, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    placed = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), args)

    count = lambda: [global_stats.count(f"ce_{path}_layers") for path in ("hoisted_rows", "batch_major")]
    before = count()
    step = make_train_step(net, opt, extra_metrics=default_metrics_fn(net.topology))
    text = step.trace(*placed).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert [a - b_ for a, b_ in zip(count(), before)] == ([1, 0] if rows == "exposed" else [0, 1])
    copied = []
    for line in text.splitlines():
        shape = re.search(r"= \w+\[([0-9,]+)\]\S* copy\(", line)
        if shape and np.prod([int(d) for d in shape.group(1).split(",")]) == vocab * b * t:
            copied.append(line.strip()[:120])
    assert bool(copied) == (rows == "withheld"), copied


def test_the_hybrid_step_compiled_for_a_v5e_takes_the_grouped_kernels_and_lowers_them_once_for_all_layers(v5e, monkeypatch):
    """`nemotron-train-2k`'s expert layers at the cell's widths (2 rows of
    2,048 tokens, top 6 of 128 experts, 8 held, 2688 x 1856: passes of 3,072
    rows) in a training step compiled for one described v5e: every layer
    takes the Pallas grouped products (`moe_grouped_kernel_layers` n / 0, no
    `ragged-dot` left in the program), whose blocks fit the chip's VMEM inside
    the step.  The kernels (product, row gradient, matrices' gradient, of w1
    and of w2) are jitted functions, so the lowered module holds the same few
    whatever the number of layers (a lowering costs a warm boot its Python:
    PERF.md section 6, PR 35 and 39), where the compiled program holds every
    call site's: a layer's forward 2, recomputed forward 2, row gradients 2,
    matrices' gradients 2, and the same again in the body of each loop over
    the passes beyond the first."""
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.models.hybrid_lm import hybrid_lm_cost
    from paddle_tpu.trainer.step import make_train_step
    from paddle_tpu.utils.timers import global_stats

    one_chip = SingleDeviceSharding(v5e.devices[0])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    count = lambda: [global_stats.count(f"moe_grouped_{path}_layers") for path in ("kernel", "xla")]

    def lowered(pattern):
        reset_auto_names()
        cost, _ = hybrid_lm_cost(
            pattern, 256, 2688, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=8,
            attn_heads=2, attn_kv_heads=1, attn_head_dim=128, num_experts=128, experts_per_token=6,
            expert_hidden=1856, shared_hidden=128, experts_held=(0, 8))
        net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
        opt = paddle.optimizer.Adam(learning_rate=1e-4)
        params, state = jax.eval_shape(net.init, jax.random.PRNGKey(0))
        ids = SeqTensor(jax.ShapeDtypeStruct((2, 2048), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.int32))
        args = (params, state, jax.eval_shape(opt.init, params), {"word": ids, "next_word": ids},
                jax.eval_shape(lambda: jax.random.PRNGKey(0)))
        placed = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), args)
        before = count()
        low = make_train_step(net, opt).trace(*placed).lower(lowering_platforms=("tpu",))
        assert [a - b for a, b in zip(count(), before)] == [len(pattern), 0]
        return low

    one, two = lowered("E"), lowered("EE")
    kernels = one.as_text().count("tpu_custom_call")
    assert 6 <= kernels <= 12 and two.as_text().count("tpu_custom_call") == kernels
    text = two.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * 2 * 8 and "ragged-dot" not in text
