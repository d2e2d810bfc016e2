"""Batch value types — the TPU-native replacement for the reference's
``Argument`` (reference: paddle/parameter/Argument.h:29-157).

The reference packs variable-length sequences into CSR form (`value` rows +
`sequenceStartPositions`).  Static XLA shapes want padded tensors, so the
in-graph value type is :class:`SeqTensor`: a padded array plus optional
per-sample lengths (and sub-sequence segment ids for nested sequences).
All layer implementations consume and produce SeqTensors.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


class RowBound(NamedTuple):
    """A static bound on the true tokens of a sequence slot: split its
    ``batch`` rows into ``shards`` equal blocks of consecutive rows (the
    blocks a data mesh gives each device), and no block holds more than
    ``rows`` of its T x batch/shards positions inside a sequence.  The feeder
    sets it (:func:`row_bound`) on the slots a recurrent_group with a hoisted
    epilogue scans; the group then runs that epilogue on ``rows`` rows a
    shard instead of all of them (layers/recurrent_group.py
    ``_HoistRows.kept``)."""

    batch: int
    shards: int
    rows: int


@jax.tree_util.register_pytree_node_class
class SeqTensor:
    """A (possibly sequential) batch value.

    data:        [B, ...] for plain samples; [B, T, ...] padded when seq;
                 [B, S, T, ...] doubly padded for nested sequences (a sequence
                 of subsequences — the reference's SUB_SEQUENCE slots).
    lengths:     [B] int32 — valid timesteps (plain seq) or valid subsequence
                 count (nested), or None for non-sequence.
    sub_lengths: [B, S] int32 valid-timestep counts of each subsequence, or
                 None.  Replaces the reference's CSR
                 subSequenceStartPositions (Argument.h:84-93) — static doubly
                 padded shapes instead of two-level offset vectors, so nested
                 recurrence lowers to a lax.scan over S whose body sees an
                 ordinary [B, T, ...] sequence.
    """

    def __init__(self, data, lengths=None, sub_lengths=None, sparse_ids=False,
                 row_bound=None):
        self.data = data
        self.lengths = lengths
        self.sub_lengths = sub_lengths
        # True when `data` is the PADDED-ID form of a big-vocab sparse
        # slot ([..., max_nnz] int32 ids, sentinel == vocab) — set by the
        # feeder, consumed by fc/mixed projections via
        # layers.base.is_sparse_ids (exact dispatch, no shape heuristics)
        self.sparse_ids = sparse_ids
        # a RowBound on the true tokens of a data slot, or None — set by the
        # feeder, static like sparse_ids, so that two bounds are two shapes
        self.row_bound = row_bound

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.data, self.lengths, self.sub_lengths)
        return children, (self.sparse_ids, self.row_bound)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, sparse_ids=bool(aux[0]), row_bound=aux[1])

    # -- helpers ------------------------------------------------------------
    @property
    def is_seq(self) -> bool:
        return self.lengths is not None

    @property
    def is_nested(self) -> bool:
        return self.sub_lengths is not None

    @property
    def batch_size(self) -> int:
        return self.data.shape[0]

    @property
    def max_len(self) -> int:
        """Extent of the outer padded axis: T (plain seq) or S (nested)."""
        assert self.is_seq
        return self.data.shape[1]

    @property
    def max_sub_len(self) -> int:
        assert self.is_nested
        return self.data.shape[2]

    def mask(self, dtype=jnp.float32):
        """[B, T] (or [B, S] for nested) 1/0 validity of the outer axis."""
        assert self.is_seq
        t = jnp.arange(self.max_len, dtype=jnp.int32)
        return (t[None, :] < self.lengths[:, None]).astype(dtype)

    def sub_mask(self, dtype=jnp.float32):
        """[B, S, T] joint validity: subsequence s valid AND timestep t valid
        within it."""
        assert self.is_nested
        outer = self.mask(dtype)  # [B, S]
        t = jnp.arange(self.max_sub_len, dtype=jnp.int32)
        inner = (t[None, None, :] < self.sub_lengths[:, :, None]).astype(dtype)
        return outer[:, :, None] * inner

    def masked_data(self):
        """data with padding timesteps zeroed."""
        if not self.is_seq:
            return self.data
        m = self.sub_mask(self.data.dtype) if self.is_nested else self.mask(self.data.dtype)
        return self.data * m.reshape(m.shape + (1,) * (self.data.ndim - m.ndim))

    def with_data(self, data) -> "SeqTensor":
        return SeqTensor(data, self.lengths, self.sub_lengths)

    def __repr__(self) -> str:  # pragma: no cover
        shp = getattr(self.data, "shape", None)
        return f"SeqTensor(shape={shp}, seq={self.is_seq}, nested={self.is_nested})"


Batch = Dict[str, SeqTensor]  # slot name -> value, the feeder's output


# ---------------------------------------------------------------------------
# Bucket-shape canonicalization — the feed→compile→scan shape-ladder contract
# ---------------------------------------------------------------------------
# Variable-length workloads recompile the jitted step once per distinct batch
# shape.  The contract threaded through reader.bucketing → DataFeeder →
# trainer.step → layers.recurrent_group is: every padded sequence extent is a
# rung of one small geometric ladder (16·2^k), so the jit cache sees a
# bounded shape set no matter how lengths are distributed, and the token-
# budget batcher (reader/bucketing.py) keeps tokens/step ~constant by scaling
# batch size inversely with the rung.

DEFAULT_LADDER: Tuple[int, ...] = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

# Nested sequences' S axis (subsequence count) is typically small (2-8);
# rounding it on the 16-based time ladder would pad the common case 4-8x.
# A 4-based ladder bounds the shape set just as well without the blowup.
DEFAULT_SUB_LADDER: Tuple[int, ...] = (4, 8, 16, 32, 64, 128, 256, 512, 1024)

# The BATCH axis is a compiled extent exactly like the padded time axis: a
# caller feeding ragged batch sizes (inference.Inference, the serving plane's
# ragged live-slot set) retraces per distinct B unless B rides a ladder too.
# Batches start at 1 (single-request decode) so the ladder is 2^k, not 16·2^k.
DEFAULT_BATCH_LADDER: Tuple[int, ...] = (
    1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
)


def shape_ladder(base: int = 16, rungs: int = 9) -> Tuple[int, ...]:
    """Geometric shape ladder base·2^k, k in [0, rungs)."""
    return tuple(base << k for k in range(rungs))


def ladder_len(n: int, ladder: Sequence[int] = DEFAULT_LADDER) -> int:
    """Smallest ladder rung >= n; past the top rung, the next multiple of it
    (so absurdly long outliers still get a canonical — if coarse — shape)."""
    n = max(int(n), 1)
    for r in ladder:
        if n <= r:
            return r
    top = ladder[-1]
    return ((n + top - 1) // top) * top


def row_bound(lengths, t: int, shards: int = 1) -> Optional[RowBound]:
    """The RowBound of a [B] lengths vector padded to T, over ``shards``
    blocks of rows (one block where they do not divide B, as the hoisted
    rows' order has it): the largest block's token count rounded up to a
    rung, the smallest multiple of ceil(T*B/shards / 16) that covers it,
    rounded up to 128 rows — at most 16 bounds for one (T, B), as the time
    ladder bounds T.  None where that leaves no row of a block out."""
    import numpy as np

    b = len(lengths)
    n = shards if shards > 1 and b % shards == 0 else 1
    block = int(t) * b // n
    if not block:
        return None
    count = int(np.minimum(np.asarray(lengths), t).reshape(n, -1).sum(1).max())
    unit = -(-block // 16)
    rung = max(-(-count // unit), 1) * unit
    rows = -(-rung // 128) * 128
    return RowBound(b, n, rows) if rows < block else None


def batch_shape_key(batch: Batch) -> tuple:
    """Hashable shape signature of a feeder batch — exactly the part of the
    jit cache key the feed controls (slot names, data shapes, dtypes, and a
    slot's RowBound where it has one).  Two batches with equal keys dispatch
    to the same compiled executable."""
    key = []
    for name in sorted(batch):
        t = batch[name]
        data = t.data if hasattr(t, "data") else t
        entry = (name, tuple(int(d) for d in data.shape), str(data.dtype))
        bound = getattr(t, "row_bound", None)
        key.append(entry if bound is None else entry + (bound,))
    return tuple(key)


def _pad_axis(data, axis: int, to: int):
    """Zero-pad one axis of a host/device array up to `to` (no-op if equal).
    Works on numpy and jax arrays alike (the feeder hands numpy; bench and
    tests may hand staged device arrays)."""
    cur = data.shape[axis]
    if cur >= to:
        return data
    import numpy as np

    pad = [(0, 0)] * data.ndim
    pad[axis] = (0, to - cur)
    mod = jnp if isinstance(data, jax.Array) else np
    return mod.pad(data, pad)


def canonicalize_batch(
    batch: Batch, ladder: Sequence[int] = DEFAULT_LADDER
) -> Batch:
    """Round every sequence slot's padded extents up to the shape ladder.

    Plain sequences pad T (axis 1) to ``ladder_len(T)``; nested sequences pad
    both S (axis 1) and T (axis 2).  Lengths are untouched — the new
    positions are beyond every sample's valid range, so masks, cost sums and
    the scan early-exit (layers/recurrent_group.py) all treat them as dead
    padding, and a slot's RowBound still holds.  Zero-pad is correct for every slot kind here: the added
    positions are whole masked-out timesteps, not intra-step nnz slots (the
    feeder's sparse-ids sentinel concern)."""
    out: Batch = {}
    for name, t in batch.items():
        if not hasattr(t, "data") or not t.is_seq:
            out[name] = t
            continue
        sub_lengths = t.sub_lengths
        if t.is_nested:
            # S axis (outer, axis 1) rounds on the shallow sub-ladder; T
            # (axis 2) on the time ladder
            data = _pad_axis(
                t.data, 1, ladder_len(t.data.shape[1], DEFAULT_SUB_LADDER)
            )
            data = _pad_axis(data, 2, ladder_len(data.shape[2], ladder))
            # sub_lengths must track the padded S axis (new subsequences
            # are empty: zero valid timesteps) or mask consumers see an
            # internally inconsistent SeqTensor
            sub_lengths = _pad_axis(sub_lengths, 1, data.shape[1])
        else:
            data = _pad_axis(t.data, 1, ladder_len(t.data.shape[1], ladder))
        out[name] = SeqTensor(
            data, t.lengths, sub_lengths, sparse_ids=t.sparse_ids,
            row_bound=t.row_bound,
        )
    return out


def pad_batch_rows(batch: Batch, to_b: int) -> Batch:
    """Pad every slot's BATCH axis (axis 0) up to ``to_b`` dead rows.

    The batch-axis half of the ladder contract (DEFAULT_BATCH_LADDER):
    callers with ragged batch sizes pad B to a rung, dispatch ONE compiled
    program, and slice the leading ``b`` rows back out of every output
    (:func:`slice_batch_rows`).  Padded rows are all-zero data; sequence
    lengths pad with 1 — one valid zero timestep — so per-row normalizers
    (mean pooling's divide-by-length) never see 0/0 in the dead rows.  Row
    independence of the forward pass keeps the live rows bit-identical."""
    out: Batch = {}
    for name, t in batch.items():
        if not hasattr(t, "data"):
            out[name] = t
            continue
        b = t.data.shape[0]
        if b >= to_b:
            out[name] = t
            continue
        data = _pad_axis(t.data, 0, to_b)
        lengths = t.lengths
        sub_lengths = t.sub_lengths
        import numpy as np

        mod = jnp if isinstance(t.data, jax.Array) else np
        if lengths is not None:
            pad_len = mod.ones((to_b - b,), dtype=lengths.dtype)
            lengths = mod.concatenate([lengths, pad_len], axis=0)
        if sub_lengths is not None:
            pad_sub = mod.ones(
                (to_b - b,) + tuple(sub_lengths.shape[1:]), dtype=sub_lengths.dtype
            )
            sub_lengths = mod.concatenate([sub_lengths, pad_sub], axis=0)
        out[name] = SeqTensor(data, lengths, sub_lengths, sparse_ids=t.sparse_ids)
    return out


def slice_batch_rows(outs: Dict[str, SeqTensor], b: int) -> Dict[str, SeqTensor]:
    """Undo :func:`pad_batch_rows` on a dict of output SeqTensors: keep the
    first ``b`` rows of data/lengths/sub_lengths."""
    sliced: Dict[str, SeqTensor] = {}
    for name, t in outs.items():
        if not hasattr(t, "data"):
            sliced[name] = t
            continue
        sliced[name] = SeqTensor(
            t.data[:b],
            None if t.lengths is None else t.lengths[:b],
            None if t.sub_lengths is None else t.sub_lengths[:b],
            sparse_ids=t.sparse_ids,
        )
    return sliced


def non_seq(data) -> SeqTensor:
    return SeqTensor(jnp.asarray(data))


def seq(data, lengths) -> SeqTensor:
    return SeqTensor(jnp.asarray(data), jnp.asarray(lengths, dtype=jnp.int32))


def nested_seq(data, n_sub, sub_lengths) -> SeqTensor:
    """[B, S, T, ...] doubly-padded nested sequence: n_sub[B] valid
    subsequences, sub_lengths[B, S] valid timesteps per subsequence."""
    return SeqTensor(
        jnp.asarray(data),
        jnp.asarray(n_sub, dtype=jnp.int32),
        jnp.asarray(sub_lengths, dtype=jnp.int32),
    )
