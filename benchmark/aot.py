"""Rehearsal without the chip: compiles a cell's train step at its real size
for a described v5e 2x2 host and prints what the TPU's compiler says of it.

    python3 benchmark/aot.py nmt-train-dp4

One JSON line: XLA's `memory_analysis()` per device (temp, arguments), the
collectives the compiler put in, the Pallas kernels (`tpu_custom_call`).
Nothing runs, so this gives no time and no result; a compile that passes is
not a chip run.  It reaches into the trainer (`_make_feeder`, `_train_step`)
because the public path builds its mesh from devices that are attached.
"""

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(TPU_SKIP_MDS_QUERY="1", TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", JAX_PLATFORMS="cpu")

import run  # noqa: E402  (puts the benchmark and the program on sys.path)


def main(workload):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import paddle_tpu as paddle
    import refsteps
    import traffic
    from paddle_tpu.core.topology import reset_auto_names
    from paddle_tpu.parallel.mesh import make_mesh

    # such a compile is written to the persistent cache and cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    _, cell, cfg, mix, _ = run.load_cell(workload)
    chips = cell["chips"]
    paddle.init(compute_dtype=cfg["compute_dtype"], seed=0)
    reset_auto_names()
    cost, feeding = refsteps.load_by_name("models", cfg["model"]).build(cfg)
    mesh = make_mesh(data=chips, devices=topo.devices[:chips]) if chips > 1 else None
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=paddle.parameters.create(cost, seed=0), mesh=mesh,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-3))
    fed = trainer._make_feeder(feeding)(traffic.kind(mix).make_corpus(dict(mix, corpus_batches=1), cfg, 1)[0])
    if mesh is None:
        whole = rows = SingleDeviceSharding(topo.devices[0])
    else:
        whole, rows = NamedSharding(mesh, PartitionSpec()), NamedSharding(mesh, PartitionSpec("data"))

    def shapes(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype, sharding=sharding), tree)

    compiled = trainer._train_step.trace(
        shapes(trainer.parameters.params, whole), shapes(trainer.parameters.state, whole),
        shapes(trainer._opt_state, whole), shapes(fed, rows), shapes(jax.random.PRNGKey(0), whole),
    ).lower(lowering_platforms=("tpu",)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    print(json.dumps({
        "cell": workload, "chips": chips,
        "temp_GiB": memory.temp_size_in_bytes / 2 ** 30,
        "arguments_GiB": memory.argument_size_in_bytes / 2 ** 30,
        "collectives": {k: len(re.findall(rf" {k}(?:-start)?\(", text))
                        for k in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")},
        "gathered": sorted(set(re.findall(r"= (\S+?)\{[^ ]* all-gather(?:-start)?\(", text))),
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))


if __name__ == "__main__":
    main(sys.argv[1])
