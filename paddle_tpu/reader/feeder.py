"""DataFeeder — python samples → padded static-shape device batches.

Replaces the reference's DataProviderConverter (reference:
paddle/py_paddle/dataprovider_converter.py:247) which packed samples into
CSR Arguments.  TPU-native contract instead: every sequence slot is padded to
a *bucketed* max length (rounded up to a multiple of ``seq_multiple``) so jit
sees a small, bounded set of shapes; lengths ride alongside as int32 vectors
(SeqTensor).  Sparse slots are densified to multi-hot rows (gather-sharded
embedding inputs use INDEX slots instead, which stay ids).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from paddle_tpu.core.batch import SeqTensor, ladder_len, row_bound
from paddle_tpu.core.data_types import InputType, SeqLevel, SlotKind


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# Above this vocabulary size a sparse_binary slot feeds as PADDED IDS
# (sentinel = dim) instead of a dense multi-hot row: the multi-hot form is
# O(B·T·vocab) memory — fatal for the reference's 1.45M-word LTR configs —
# while the id form is O(B·T·max_nnz) and consumers gather-sum touched rows
# (layers/base.py gather_sum_rows; the reference's SparseRowMatrix regime).
SPARSE_IDS_THRESHOLD = 65536


def _ids_form(itype: InputType) -> bool:
    return (
        itype.kind == SlotKind.SPARSE_BINARY
        and itype.dim > SPARSE_IDS_THRESHOLD
    )


def feed_dtypes_of(topology) -> Dict[str, str]:
    """{slot: wire dtype} for data layers declaring a narrow feed dtype
    (``data_layer(feed_dtype="uint8")``) — shared by the trainer and the
    inference face so train and infer see identical device-side values."""
    return {
        name: conf.attr("feed_dtype")
        for name, conf in topology.data_layers().items()
        if conf.attr("feed_dtype")
    }


class DataFeeder:
    """feeding: [(slot_name, InputType)] in sample-tuple order, or a dict
    {slot_name: index_in_sample} combined with `data_types`."""

    def __init__(
        self,
        data_types: Sequence[Tuple[str, InputType]],
        feeding: Optional[Union[Dict[str, int], Sequence[str]]] = None,
        seq_multiple: int = 8,
        min_seq_len: int = 8,
        dtype=np.float32,
        feed_dtypes: Optional[Dict[str, Any]] = None,
        ladder: Optional[Sequence[int]] = None,
        row_bound_slots: Sequence[str] = (),
        shards: int = 1,
    ):
        """feed_dtypes: per-slot WIRE dtype override for dense slots (e.g.
        {"image": np.uint8}) — the batch crosses host->device at 1/4 the
        bytes and the jitted step casts + normalizes on device (the data
        layer's feed_scale/feed_shift attrs; reference DataProvider ships
        bytes the same way, mnist_bin_part is uint8 on disk).

        ladder: canonical sequence-length rungs (core.batch.DEFAULT_LADDER)
        replacing the multiple-of-``seq_multiple`` rounding — the feed half
        of the bucket-shape contract: padded lengths come from a small
        geometric set, so the jitted step's shape cache stays bounded over
        any length distribution (pair with reader.bucketing batches that
        fill a token budget per rung).

        row_bound_slots: sequence slots that get a RowBound on their true
        tokens over ``shards`` blocks of rows (core.batch.row_bound) — the
        slots a network's hoisted recurrent_group epilogues scan
        (CompiledNetwork.row_bound_slots), with the data mesh's size."""
        self.data_types = list(data_types)
        self.row_bound_slots = frozenset(row_bound_slots)
        self.shards = shards
        self.feed_dtypes = dict(feed_dtypes or {})
        if feeding is None:
            self.index = {name: i for i, (name, _) in enumerate(self.data_types)}
        elif isinstance(feeding, dict):
            self.index = dict(feeding)
        else:
            self.index = {name: i for i, name in enumerate(feeding)}
        self.seq_multiple = seq_multiple
        self.min_seq_len = min_seq_len
        self.ladder = tuple(ladder) if ladder else None
        self.dtype = dtype

    # ------------------------------------------------------------------
    def __call__(self, batch_data: List[Any]) -> Dict[str, SeqTensor]:
        return self.convert(batch_data)

    def convert(self, batch_data: List[Any]) -> Dict[str, SeqTensor]:
        n_slots = max(self.index.values()) + 1 if self.index else 0
        for sample in batch_data[:1]:
            if not isinstance(sample, (tuple, list)) or len(sample) < n_slots:
                raise ValueError(
                    f"each sample must be a tuple of {n_slots} slot(s) "
                    f"({[n for n, _ in self.data_types]}); got "
                    f"{type(sample).__name__}. Did you forget to wrap the "
                    f"reader with paddle.batch(reader, batch_size)?"
                )
        out: Dict[str, SeqTensor] = {}
        for name, itype in self.data_types:
            col = [sample[self.index[name]] for sample in batch_data]
            t = self._convert_slot(
                col, itype, self.feed_dtypes.get(name, self.dtype)
            )
            if name in self.row_bound_slots and itype.seq == SeqLevel.SEQ:
                t.row_bound = row_bound(t.lengths, t.data.shape[1], self.shards)
            out[name] = t
        return out

    # ------------------------------------------------------------------
    def _bucket_len(self, max_len: int) -> int:
        if self.ladder:
            return ladder_len(max(max_len, self.min_seq_len), self.ladder)
        return max(_round_up(max_len, self.seq_multiple), self.min_seq_len)

    def _convert_slot(
        self, col: List[Any], itype: InputType, dtype=None
    ) -> SeqTensor:
        dtype = self.dtype if dtype is None else dtype
        if itype.seq == SeqLevel.NONE:
            return self._convert_plain(col, itype, dtype)
        if itype.seq == SeqLevel.SEQ:
            return self._convert_seq(col, itype, dtype)
        return self._convert_sub_seq(col, itype, dtype)

    def _convert_plain(self, col, itype: InputType, dtype=None) -> SeqTensor:
        dtype = self.dtype if dtype is None else dtype
        b = len(col)
        if itype.kind == SlotKind.DENSE:
            arr = np.asarray(col, dtype=dtype).reshape(b, itype.dim)
            return SeqTensor(arr)
        if itype.kind == SlotKind.INDEX:
            return SeqTensor(np.asarray(col, dtype=np.int32).reshape(b))
        if _ids_form(itype):
            nnz = max(
                _round_up(max((len(ids) for ids in col), default=1), 8), 8
            )
            arr = np.full((b, nnz), itype.dim, dtype=np.int32)  # sentinel pad
            for i, ids in enumerate(col):
                arr[i, : len(ids)] = np.asarray(ids, dtype=np.int32)
            return SeqTensor(arr, sparse_ids=True)
        # sparse -> dense multi-hot
        arr = np.zeros((b, itype.dim), dtype=self.dtype)
        for i, ids in enumerate(col):
            if itype.kind == SlotKind.SPARSE_BINARY:
                arr[i, np.asarray(ids, dtype=np.int64)] = 1.0
            else:
                idx, vals = zip(*ids) if ids else ((), ())
                arr[i, np.asarray(idx, dtype=np.int64)] = np.asarray(vals, self.dtype)
        return SeqTensor(arr)

    def _convert_seq(self, col, itype: InputType, dtype=None) -> SeqTensor:
        dtype = self.dtype if dtype is None else dtype
        b = len(col)
        lengths = np.asarray([len(s) for s in col], dtype=np.int32)
        t = self._bucket_len(int(lengths.max()) if b else 1)
        if itype.kind == SlotKind.INDEX:
            arr = np.zeros((b, t), dtype=np.int32)
            for i, s in enumerate(col):
                arr[i, : len(s)] = np.asarray(s, dtype=np.int32)
            return SeqTensor(arr, lengths)
        if itype.kind == SlotKind.DENSE:
            arr = np.zeros((b, t, itype.dim), dtype=dtype)
            for i, s in enumerate(col):
                if len(s):
                    arr[i, : len(s)] = np.asarray(s, dtype=dtype)
            return SeqTensor(arr, lengths)
        if _ids_form(itype):
            nnz = max(
                _round_up(
                    max((len(ids) for s in col for ids in s), default=1), 8
                ),
                8,
            )
            arr = np.full((b, t, nnz), itype.dim, dtype=np.int32)
            for i, s in enumerate(col):
                for j, ids in enumerate(s):
                    arr[i, j, : len(ids)] = np.asarray(ids, dtype=np.int32)
            return SeqTensor(arr, lengths, sparse_ids=True)
        # sparse sequence -> [B, T, dim] multi-hot
        arr = np.zeros((b, t, itype.dim), dtype=self.dtype)
        for i, s in enumerate(col):
            for j, ids in enumerate(s):
                if itype.kind == SlotKind.SPARSE_BINARY:
                    arr[i, j, np.asarray(ids, dtype=np.int64)] = 1.0
                else:
                    idx, vals = zip(*ids) if ids else ((), ())
                    arr[i, j, np.asarray(idx, dtype=np.int64)] = np.asarray(
                        vals, self.dtype
                    )
        return SeqTensor(arr, lengths)

    def _convert_sub_seq(self, col, itype: InputType, dtype=None) -> SeqTensor:
        """Nested sequences: each sample is a list of subsequences.  Reference
        packs these as two-level CSR (Argument.h:84-93,
        subSequenceStartPositions); TPU-native form is a doubly padded
        [B, S, T, ...] block plus n_sub[B] and sub_lengths[B, S] so nested
        recurrence stays static-shape under jit."""
        dtype = self.dtype if dtype is None else dtype
        b = len(col)
        n_sub = np.asarray([len(s) for s in col], dtype=np.int32)
        raw_s = int(n_sub.max()) if b else 1
        if self.ladder:
            # the S axis is a compiled extent too: ladder it so nested
            # batches keep the bounded-shape contract — on the shallow
            # 4-based sub-ladder, since subsequence counts are usually
            # small and the 16-based time ladder would pad them 4-8x
            from paddle_tpu.core.batch import DEFAULT_SUB_LADDER

            s_max = ladder_len(max(raw_s, 1), DEFAULT_SUB_LADDER)
        else:
            s_max = max(_round_up(raw_s, 4), 4)
        sub_lengths = np.zeros((b, s_max), dtype=np.int32)
        max_t = 1
        for i, sample in enumerate(col):
            for j, sub in enumerate(sample):
                sub_lengths[i, j] = len(sub)
                max_t = max(max_t, len(sub))
        t = self._bucket_len(max_t)
        if itype.kind == SlotKind.INDEX:
            arr = np.zeros((b, s_max, t), dtype=np.int32)
            for i, sample in enumerate(col):
                for j, sub in enumerate(sample):
                    arr[i, j, : len(sub)] = np.asarray(sub, dtype=np.int32)
            return SeqTensor(arr, n_sub, sub_lengths)
        if _ids_form(itype):
            nnz = max(
                _round_up(
                    max(
                        (len(ids) for s in col for sub in s for ids in sub),
                        default=1,
                    ),
                    8,
                ),
                8,
            )
            arr = np.full((b, s_max, t, nnz), itype.dim, dtype=np.int32)
            for i, sample in enumerate(col):
                for j, sub in enumerate(sample):
                    for k, ids in enumerate(sub):
                        arr[i, j, k, : len(ids)] = np.asarray(ids, np.int32)
            return SeqTensor(arr, n_sub, sub_lengths, sparse_ids=True)
        arr = np.zeros((b, s_max, t, itype.dim), dtype=dtype)
        for i, sample in enumerate(col):
            for j, sub in enumerate(sample):
                if itype.kind == SlotKind.DENSE:
                    if len(sub):
                        arr[i, j, : len(sub)] = np.asarray(sub, dtype=dtype)
                else:
                    for k, ids in enumerate(sub):
                        if itype.kind == SlotKind.SPARSE_BINARY:
                            arr[i, j, k, np.asarray(ids, dtype=np.int64)] = 1.0
                        else:
                            idx, vals = zip(*ids) if ids else ((), ())
                            arr[i, j, k, np.asarray(idx, dtype=np.int64)] = (
                                np.asarray(vals, self.dtype)
                            )
        return SeqTensor(arr, n_sub, sub_lengths)
