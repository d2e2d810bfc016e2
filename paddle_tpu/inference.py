"""paddle.infer / Inference — the v2 inference user surface (reference:
python/paddle/v2/inference.py:8-87; C ABI paddle/capi/gradient_machine.h:27-86).

The reference builds a testing-mode GradientMachine and feeds CSR arguments;
here the topology compiles to ONE jitted XLA forward (cached per batch
shape — the feeder's bucketed padding keeps the shape set small) and field
extraction unpads sequence outputs back to the reference's concatenated-rows
convention.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from paddle_tpu.core.batch import (
    DEFAULT_BATCH_LADDER,
    DEFAULT_LADDER,
    SeqTensor,
    ladder_len,
    pad_batch_rows,
    slice_batch_rows,
)
from paddle_tpu.core.compiler import CompiledNetwork, get_default_compute_dtype
from paddle_tpu.core.topology import LayerOutput, Topology

__all__ = ["infer", "Inference"]


def _extract_field(out: SeqTensor, field: str) -> np.ndarray:
    """reference forwardTest fields: 'value' (activations / scores) and 'id'
    (integer outputs).  Sequence outputs are unpadded to the reference's
    concatenated-valid-rows form; nested outputs concatenate both levels."""
    data = np.asarray(out.data)
    if field == "id":
        data = data.astype(np.int64)
    if not out.is_seq:
        return data
    lengths = np.asarray(out.lengths)
    rows: List[np.ndarray] = []
    if out.is_nested:
        sub_lengths = np.asarray(out.sub_lengths)
        for i in range(data.shape[0]):
            for j in range(int(lengths[i])):
                rows.append(data[i, j, : int(sub_lengths[i, j])])
    else:
        for i in range(data.shape[0]):
            rows.append(data[i, : int(lengths[i])])
    return np.concatenate(rows, axis=0) if rows else data[:0].reshape(0, *data.shape[2:])


class Inference:
    """Compiled inference over one or more output layers.

    ::

        inferer = Inference(output_layer=prediction, parameters=parameters)
        probs = inferer.infer(input=samples)
    """

    def __init__(
        self,
        output_layer: Union[LayerOutput, Sequence[LayerOutput]],
        parameters,
    ):
        outs = (
            list(output_layer)
            if isinstance(output_layer, (list, tuple))
            else [output_layer]
        )
        self.output_names = [o.name for o in outs]
        self.topology = Topology(outs)
        self.network = CompiledNetwork(
            self.topology, compute_dtype=get_default_compute_dtype()
        )
        if not hasattr(parameters, "network"):
            # topology-free bag from the static Parameters.from_tar(f):
            # build parameters for this inference topology, merge by name
            from paddle_tpu.parameters import create_from_network

            detached = parameters
            parameters = create_from_network(self.network, seed=0)
            detached.merge_into(parameters)
        # inherit the training network's mesh so mesh-aware layers (ring
        # attention) keep their parallelism at inference time
        self.network.mesh = getattr(parameters.network, "mesh", None)
        # Parameters may come from a larger (training) topology; apply() looks
        # up layers by name, so the superset simply carries unused entries.
        self._params = parameters.params
        self._state = parameters.state

        # distinct compiled variants this instance has traced — the
        # compile-count regression surface: with the batch-rung + sequence-
        # ladder canonicalization below, repeated infer() calls with varying
        # batch sizes/lengths stay bounded by the rungs they realize,
        # instead of retracing per distinct shape
        self.trace_count = 0

        def fwd(params, state, batch):
            self.trace_count += 1
            all_outs, _ = self.network.apply(params, batch, state=state, train=False)
            # Keep auxiliary side outputs of the selected layers too
            # ("<name>@scores" from beam_search, "<name>@cell" from lstm_step),
            # but only those that ARE batch-major values: a group's
            # "@logits_rows" holds what "@logits" holds in another row order
            keep = set(self.output_names)
            return {
                n: v
                for n, v in all_outs.items()
                if (n in keep or n.split("@")[0] in keep)
                and isinstance(v, SeqTensor)
            }

        self._fwd = jax.jit(fwd)

    # ------------------------------------------------------------------
    def iter_infer(
        self,
        input: Sequence[Any],
        feeding=None,
        batch_size: Optional[int] = None,
    ):
        from paddle_tpu.reader.feeder import DataFeeder, feed_dtypes_of

        if not len(input):
            raise ValueError("infer() needs at least one input sample")
        # same wire dtypes as training (narrow uint8 feeds normalize on
        # device via the data layer's feed_scale/feed_shift) — a float-fed
        # batch would skip the on-device normalize and skew inference.
        # Sequence extents ride the canonical shape ladder and the BATCH
        # axis pads to a DEFAULT_BATCH_LADDER rung (dead rows sliced back
        # off every output), so repeated inference with ragged batch
        # sizes/lengths dispatches a BOUNDED set of compiled variants
        # (core/batch.py; `trace_count` counter-asserts it in tests).
        feeder = DataFeeder(
            self.topology.data_types(), feeding,
            feed_dtypes=feed_dtypes_of(self.topology),
            ladder=DEFAULT_LADDER,
        )
        # chunk at the top batch rung: an oversized batch runs as exact
        # full rungs + one padded remainder, instead of padding the whole
        # thing up to the next multiple of the top rung
        bs = min(batch_size or len(input), DEFAULT_BATCH_LADDER[-1])
        for lo in range(0, len(input), bs):
            rows = list(input[lo : lo + bs])
            batch = pad_batch_rows(
                feeder(rows), ladder_len(len(rows), DEFAULT_BATCH_LADDER)
            )
            outs = self._fwd(self._params, self._state, batch)
            yield slice_batch_rows(outs, len(rows))

    def iter_infer_field(self, field, **kwargs):
        fields = list(field) if isinstance(field, (list, tuple)) else [field]
        for result in self.iter_infer(**kwargs):
            yield [
                _extract_field(result[name], f)
                for name in self.output_names
                for f in fields
            ]

    def infer(
        self,
        input: Sequence[Any],
        field: Union[str, Sequence[str]] = "value",
        feeding=None,
        batch_size: Optional[int] = None,
    ):
        """Returns one ndarray per (output_layer × field), concatenated over
        batches; a single array when there is exactly one."""
        collected: Optional[List[List[np.ndarray]]] = None
        for res in self.iter_infer_field(
            field=field, input=input, feeding=feeding, batch_size=batch_size
        ):
            if collected is None:
                collected = [[] for _ in res]
            for i, item in enumerate(res):
                collected[i].append(item)
        assert collected, "empty input"
        merged = [np.concatenate(c, axis=0) for c in collected]
        return merged[0] if len(merged) == 1 else merged


def infer(output_layer, parameters, input, feeding=None, field="value",
          batch_size: Optional[int] = None):
    """One-shot inference (reference paddle.infer, v2/inference.py:87)."""
    return Inference(output_layer, parameters).infer(
        input=input, field=field, feeding=feeding, batch_size=batch_size
    )
