"""Metric builders attached to the train/eval step — the in-graph half of the
reference evaluator framework (reference: paddle/gserver/evaluators/
Evaluator.cpp classification_error:995, sum:996, precision_recall:584).

Metrics here are computed *inside* the jitted step from layer outputs (no
host sync), then averaged across batches on the host by the trainer.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import Topology

_CLS_COST_TYPES = {"softmax_with_cost", "cross_entropy"}


def default_metrics_fn(topology: Topology) -> Optional[Callable]:
    """Build an extra_metrics fn: for classification costs in the topology,
    emit classification_error (argmax(pred) != label), masked over sequences
    — reference ClassificationErrorEvaluator (Evaluator.cpp:70-160)."""
    cls = [
        conf
        for conf in topology.layers.values()
        if conf.type in _CLS_COST_TYPES
    ]
    if not cls:
        return None

    def metrics(outs: Dict[str, SeqTensor]) -> Dict[str, jnp.ndarray]:
        m: Dict[str, jnp.ndarray] = {}
        for conf in cls:
            # a scope of the layers' "type:name" form: the argmax over a
            # 30k vocabulary is device time a trace should be able to name
            with jax.named_scope(f"evaluator:classification_error.{conf.name}"):
                pred_name, label_name = conf.inputs[0], conf.inputs[1]
                pred, label = outs[pred_name], outs[label_name]
                ids = label.data.astype(jnp.int32)
                if ids.ndim >= 2 and ids.shape[-1] == 1:
                    ids = ids[..., 0]
                # argmax(softmax(x)) == argmax(x): read the pre-activation aux
                # when the producer exposed one, so the error metric never
                # forces the [N, V] softmax to materialize (at a 32k MT vocab
                # that softmax is ~1 GB per step and exists ONLY for this
                # metric — the fused CE reads logits).  Where a
                # recurrent_group exposed it as the rows it was computed as,
                # take the argmax over the rows and put the one id a row into
                # [B, T] order, as the cost layer does (layers/cost.py
                # cross_entropy_apply): no reader of the [B, T, V] view is
                # left to keep its copy alive
                hoisted = outs.get(pred_name + "@logits_rows")
                if hoisted is not None:
                    top = hoisted.unfold(jnp.argmax(hoisted.rows, axis=-1))
                else:
                    lg = outs.get(pred_name + "@logits")
                    scores = lg.data if lg is not None else pred.data
                    top = jnp.argmax(scores, axis=-1)
                err = (top != ids).astype(jnp.float32)
                if pred.is_seq and err.ndim == 2:
                    mask = pred.mask()
                    err = jnp.sum(err * mask) / jnp.maximum(jnp.sum(mask), 1.0)
                else:
                    err = jnp.mean(err)
                key = (
                    "classification_error"
                    if len(cls) == 1
                    else f"classification_error/{conf.name}"
                )
                m[key] = err
        return m

    return metrics
