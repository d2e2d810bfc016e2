"""Device-resident pass cache + data echo — the TPU-native CACHE_PASS_IN_MEM.

The reference keeps pass 1's decoded samples in host RAM so later passes skip
the Python generator (``CacheType.CACHE_PASS_IN_MEM``, reference
paddle/gserver/dataproviders/PyDataProvider2.cpp:69).  On TPU the scarce
resource is not the generator but the host→device wire: the environment's
serial H2D ceiling caps the ResNet-50 pipeline at ~1/6 of what the compute
path sustains.  So the TPU-native cache keeps the decoded pass ON DEVICE:

* **Capture (epoch 1)** — every staged batch (DataFeeder output after
  ``shard_batch``/``device_put``, i.e. the *wire form*: uint8 pixels when the
  data layer declares ``feed_dtype="uint8"``, ~1 byte/px of HBM; normalize
  stays fused in the jitted step) is recorded by reference.  Nothing is
  copied — the batch the step consumes IS the cache entry (the train step
  never donates its batch argument).
* **HBM budget** — every batch is accounted (``nbytes`` over the pytree)
  against ``hbm_budget_bytes``.  Overflow ⇒ drop all held references, log a
  warning, and fall back to streaming for the rest of training; nothing
  breaks, the first epoch just stays the only feed mode.  Sizing rule:
  ``budget ≥ n_samples × bytes_per_sample(wire form)`` — e.g. uint8
  224×224×3 ImageNet is ~150 KB/image, so 4 GiB holds ~28k images; CIFAR-10
  (50k × 3 KB) fits in ~154 MB.
* **Data echo (epoch 1)** — ``echo_factor=k`` trains each transferred batch
  k times back-to-back during capture, so even the H2D-bound first epoch
  amortizes its transfers k-fold (the "data echoing" trick; see the input-
  pipeline-bottleneck discussion in the TensorFlow paper §data prefetching).
* **Replay (epoch ≥ 2)** — batches are re-yielded in an order drawn from
  ``jax.random.permutation`` keyed by ``fold_in(PRNGKey(seed), pass_id)``:
  reproducible from the pass seed, zero H2D traffic, no per-batch Python
  feed path.  ``sample_shuffle=True`` additionally permutes rows *within*
  each batch on device (a gather — every slot of a batch shares one
  permutation so samples stay aligned across slots).
* **Per-bucket composition** — batches of different shapes (the
  ``use_bucketing`` ladder feed) coexist: each cache entry keeps its own
  shape, and the shuffle permutes across ALL buckets, so a cached bucketed
  epoch interleaves rungs exactly like a streamed shuffled one.  Bucket
  stats ride in :meth:`summary`.

Numerics are pinned: a cached epoch replays the identical device arrays the
streamed epoch trained on, so with ``shuffle=False`` the trained parameters
are bit-identical to streaming the same batches (tests/test_pass_cache.py).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Iterable, Iterator, List, Optional

import numpy as np

_log = logging.getLogger("paddle_tpu.pass_cache")

__all__ = ["PassCache", "batch_nbytes", "copy_cache_tags"]


def batch_nbytes(batch) -> int:
    """HBM bytes ONE DEVICE holds for a staged batch (the budget is
    per-device HBM): a batch sharded over the data axis counts its largest
    per-device shard, a replicated or single-device array counts its full
    bytes, and host/numpy leaves count globally (they land whole on a
    device when fed)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(batch):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            per_dev: Dict[Any, int] = {}
            for s in shards:
                nb = int(getattr(s.data, "nbytes", 0))
                per_dev[s.device] = per_dev.get(s.device, 0) + nb
            total += max(per_dev.values(), default=0)
            continue
        size = getattr(leaf, "size", None)
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if size is not None and itemsize is not None:
            total += int(size) * int(itemsize)
    return total


def copy_cache_tags(src, dst):
    """Propagate the @provider CACHE_PASS_IN_MEM tags from a reader to a
    wrapper around it (paddle.batch, token_budget_batch, any future reader
    decorator) — one place to extend when a new tag appears."""
    if getattr(src, "cache_pass_in_mem", False):
        dst.cache_pass_in_mem = True
        dst.cache_pass_shuffle = getattr(src, "cache_pass_shuffle", True)
    return dst


def _permute_rows(batch, perm):
    """Apply ONE row permutation to every slot of a batch (data, lengths,
    sub_lengths all gather the same axis-0 order, so a sample's slots stay
    aligned).  Runs on device — perm is a device array.  A slot's RowBound
    over several shards is dropped: rows that change places change shards,
    and the bound held for each shard's own rows."""
    import jax

    def drop_shard_bound(t):
        bound = getattr(t, "row_bound", None)
        if bound is not None and bound.shards > 1:
            t.row_bound = None
        return t

    out = jax.tree_util.tree_map(lambda x: x[perm], batch)
    return jax.tree_util.tree_map(
        drop_shard_bound, out, is_leaf=lambda t: hasattr(t, "row_bound")
    )


class PassCache:
    """Capture a pass of staged device batches during epoch 1, replay it
    device-resident (shuffled, reproducibly) for every later epoch.

    Parameters
    ----------
    hbm_budget_bytes:
        Cap on cached bytes; ``None`` = unbounded.  Exceeding it logs a
        warning, frees everything held, and disables the cache (streaming
        fallback) — never an error.
    echo_factor:
        Train each epoch-1 batch this many times (data echo).  1 = off.
    seed:
        Pass-shuffle seed; epoch order is ``jax.random.permutation`` keyed
        by ``fold_in(PRNGKey(seed), pass_id)``.
    shuffle:
        Permute batch replay order per epoch.  ``False`` replays capture
        order — the bit-parity mode.
    sample_shuffle:
        Also permute rows within each batch on device during replay.  Off by
        default: across-shard gathers turn into collectives on a multi-chip
        mesh, and batch-order shuffle already decorrelates epochs.
    """

    def __init__(
        self,
        hbm_budget_bytes: Optional[int] = None,
        echo_factor: int = 1,
        seed: int = 0,
        shuffle: bool = True,
        sample_shuffle: bool = False,
    ):
        self.budget = hbm_budget_bytes
        self.echo_factor = max(int(echo_factor), 1)
        self.seed = int(seed)
        self.shuffle = shuffle
        self.sample_shuffle = sample_shuffle
        self.active = True  # False after an overflow fallback
        self.ready = False  # True after a completed capture epoch
        self.nbytes = 0
        self._batches: List[Any] = []
        self._bucket_counts: Dict[tuple, int] = {}
        self._stacked = None  # capture-order stack (stacked_pass)

    @classmethod
    def from_flags(cls, reader=None, seed: Optional[int] = None,
                   echo_factor: Optional[int] = None,
                   shuffle: Optional[bool] = None) -> "PassCache":
        """The one flag→cache construction shared by every feed path
        (SGD.train, the CLI time job): budget from
        ``pass_cache_hbm_budget_mb``; seed from the ``seed`` flag unless
        the caller pins its own (the trainer passes its seed param); echo
        from ``data_echo_factor`` (overridable); shuffle from the reader's
        ``cache_pass_shuffle`` tag (a should_shuffle=False provider must
        replay in capture order)."""
        from paddle_tpu.utils import flags as _flags

        if echo_factor is None:
            echo_factor = _flags.get_flag("data_echo_factor")
        if shuffle is None:
            shuffle = bool(getattr(reader, "cache_pass_shuffle", True))
        return cls(
            hbm_budget_bytes=_flags.get_flag("pass_cache_hbm_budget_mb")
            << 20,
            echo_factor=echo_factor,
            seed=_flags.get_flag("seed") if seed is None else seed,
            shuffle=shuffle,
        )

    # -- capture ---------------------------------------------------------
    @property
    def n_batches(self) -> int:
        return len(self._batches)

    @property
    def n_buckets(self) -> int:
        return len(self._bucket_counts)

    def observe(self, batch) -> None:
        """Account + hold one staged batch; overflow disables the cache."""
        if not self.active or self.ready:
            return
        nb = batch_nbytes(batch)
        if self.budget is not None and self.nbytes + nb > self.budget:
            _log.warning(
                "pass cache over HBM budget (%d + %d > %d bytes after %d "
                "batches); falling back to streaming — every epoch will pay "
                "the host feed.  Raise pass_cache_hbm_budget_mb if the pass "
                "should fit (sizing: n_samples x bytes/sample wire form).",
                self.nbytes, nb, self.budget, self.n_batches,
            )
            self.drop()
            return
        from paddle_tpu.core.batch import batch_shape_key

        self.nbytes += nb
        self._batches.append(batch)
        key = batch_shape_key(batch) if isinstance(batch, dict) else ()
        self._bucket_counts[key] = self._bucket_counts.get(key, 0) + 1

    def capture(self, batches: Iterable) -> Iterator:
        """Wrap the epoch-1 staged-batch stream: observes each batch into
        the cache, applies data echo, and seals the cache when the epoch
        completes (an abandoned epoch never seals — a partial pass must not
        masquerade as the full one)."""
        if self.active and not self.ready and self._batches:
            # a previous capture epoch was abandoned mid-pass; restart the
            # accounting so the cache never holds a mixed partial pass
            self._batches = []
            self._bucket_counts = {}
            self.nbytes = 0
        for batch in batches:
            self.observe(batch)
            yield batch
            # echo even when the cache overflowed: echo amortizes the H2D
            # transfer of the batch in hand, which needs no cache
            for _ in range(self.echo_factor - 1):
                yield batch
        self.seal()

    def drop(self) -> None:
        """Release every held batch and disable caching (streaming mode)."""
        self.active = False
        self.ready = False
        self._batches = []
        self._bucket_counts = {}
        self._stacked = None
        self.nbytes = 0

    def seal(self) -> None:
        """Mark the captured pass complete; replay becomes available."""
        if not self.active or not self._batches:
            return
        self.ready = True
        _log.info(
            "pass cache sealed: %d batches (%d shape bucket(s)), %.1f MB "
            "device-resident; epochs >= 2 replay with zero H2D traffic",
            self.n_batches, self.n_buckets, self.nbytes / 1e6,
        )

    # -- replay ----------------------------------------------------------
    def _epoch_key(self, pass_id: int):
        import jax

        return jax.random.fold_in(jax.random.PRNGKey(self.seed), pass_id)

    def epoch_order(self, pass_id: int) -> List[int]:
        """Replay order for one epoch — an on-device
        ``jax.random.permutation`` over batch indices, fetched as ints (a
        few bytes of D2H; the data plane itself never moves)."""
        n = self.n_batches
        if not self.shuffle or n <= 1:
            return list(range(n))
        import jax

        perm = jax.random.permutation(self._epoch_key(pass_id), n)
        return [int(i) for i in np.asarray(perm)]

    def epoch(self, pass_id: int) -> Iterator:
        """Yield the cached pass for ``pass_id``, shuffled reproducibly."""
        assert self.ready, "pass cache not sealed; nothing to replay"
        if not self.sample_shuffle:
            for i in self.epoch_order(pass_id):
                yield self._batches[i]
            return
        import jax

        key = self._epoch_key(pass_id)
        for j, i in enumerate(self.epoch_order(pass_id)):
            b = self._batches[i]
            rows = _row_count(b)
            perm = jax.random.permutation(
                jax.random.fold_in(key, j + 1), rows
            )
            yield _permute_rows(b, perm)

    def stream(self, start_pass: int = 1) -> Iterator:
        """Endless cached replay: epoch(start_pass), epoch(start_pass+1), …
        — the steady-state feed of a cached training/timing loop."""
        assert self.ready, "pass cache not sealed; nothing to replay"
        p = start_pass
        while True:
            yield from self.epoch(p)
            p += 1

    def sample_batch(self):
        """One cached batch (capture order), for shape-keying the compiled
        programs that will consume this pass."""
        assert self.ready, "pass cache not sealed"
        return self._batches[0]

    def stacked(self):
        """The cached pass stacked on a leading [N, ...] axis in CAPTURE
        order — built once and held for the cache's lifetime.  Single-
        bucket only; costs one extra copy of the pass in HBM."""
        assert self.ready, "pass cache not sealed; nothing to stack"
        assert self.n_buckets <= 1, (
            "stacked() needs a single shape bucket; this cache holds "
            f"{self.n_buckets} (use epoch() for bucketed replay)"
        )
        if self._stacked is None:
            import jax
            import jax.numpy as jnp

            self._stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *self._batches
            )
        return self._stacked

    def epoch_perm(self, pass_id: int):
        """This epoch's replay order as a device int32 vector."""
        import jax.numpy as jnp

        return jnp.asarray(self.epoch_order(pass_id), jnp.int32)

    def stacked_pass(self, pass_id: int):
        """The whole cached pass stacked on a leading [N, ...] axis in this
        epoch's shuffled order — ready for ``make_multi_train_step`` so a
        full cached epoch (or several, concatenated) runs in ONE dispatch.
        Requires a single shape bucket (stacking is shape-homogeneous; the
        bucketed feed replays via :meth:`epoch` instead)."""
        import jax

        perm = self.epoch_perm(pass_id)
        return jax.tree_util.tree_map(lambda x: x[perm], self.stacked())

    # -- introspection ---------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        return {
            "active": self.active,
            "ready": self.ready,
            "n_batches": self.n_batches,
            "n_buckets": self.n_buckets,
            "mb": round(self.nbytes / 1e6, 2),
            "echo_factor": self.echo_factor,
            "budget_mb": (
                round(self.budget / 1e6, 2) if self.budget is not None else None
            ),
        }


def _row_count(batch) -> int:
    import jax

    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", None)
        if shape:
            return int(shape[0])
    return 1
