"""Checkpoint / resume subsystem.

Three planes, mirroring the reference's three checkpoint stories:

1. **v1 parameter dirs** — ``pass-%05d/`` with one binary file per parameter
   (header: int32 version, uint32 value_size, uint64 count; then raw float32)
   exactly like the reference trainer's per-pass dumps (reference:
   paddle/parameter/Parameter.cpp save/load ~250-340, trainer/ParamUtil.cpp).

2. **v2 tar** — ``Parameters.to_tar/from_tar`` (already on Parameters;
   reference python/paddle/v2/parameters.py).

3. **Full training-state checkpoints** — params + layer state + optimizer
   state + counters in one atomically-renamed step directory with CRC32 and
   a JSON meta file, optionally written by a background thread (async), with
   retention.  This is the TPU-native replacement for the Go pserver's
   shard+optimizer-state checkpoint with md5/CRC + etcd meta (reference:
   go/pserver/service.go:244-303, paddle/optimizer/serialization.h) — except
   there is no pserver: the whole jit-visible state pytree is the checkpoint.
"""

from __future__ import annotations

import json
import os
import shutil
import struct
import tempfile
import threading
import time
import zlib
from typing import Any, Dict, Optional

import jax
import numpy as np

__all__ = [
    "save_parameter_dir",
    "load_parameter_dir",
    "CheckpointManager",
]

_V1_VERSION = 0
_V1_VALUE_SIZE = 4  # float32


# ---------------------------------------------------------------------------
# Plane 1: v1 per-parameter binary files
# ---------------------------------------------------------------------------

def save_parameter_dir(parameters, dirname: str) -> None:
    """One file per parameter named by its flattened key, v1 header layout."""
    os.makedirs(dirname, exist_ok=True)
    for name in parameters.names():
        arr = np.asarray(parameters.get(name), dtype=np.float32)
        with open(os.path.join(dirname, name.replace("/", "__")), "wb") as f:
            f.write(struct.pack("<iIQ", _V1_VERSION, _V1_VALUE_SIZE, arr.size))
            f.write(arr.tobytes())


def load_parameter_dir(parameters, dirname: str) -> None:
    for name in parameters.names():
        path = os.path.join(dirname, name.replace("/", "__"))
        with open(path, "rb") as f:
            version, value_size, count = struct.unpack("<iIQ", f.read(16))
            if version != _V1_VERSION or value_size != _V1_VALUE_SIZE:
                raise ValueError(
                    f"{path}: unsupported header version={version} "
                    f"value_size={value_size}"
                )
            data = np.frombuffer(f.read(count * value_size), dtype=np.float32)
        cur = np.asarray(parameters.get(name))
        if data.size != cur.size:
            raise ValueError(
                f"{path}: size {data.size} != parameter {name} size {cur.size}"
            )
        parameters.set(name, data.reshape(cur.shape).copy())


# ---------------------------------------------------------------------------
# Plane 3: full-state checkpoints
# ---------------------------------------------------------------------------

def _crc_file(path: str, block: int = 1 << 20) -> int:
    """Streaming CRC32 — O(1) memory for multi-GB checkpoints."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(block)
            if not chunk:
                return crc
            crc = zlib.crc32(chunk, crc)


def _flatten(tree) -> Dict[str, np.ndarray]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = np.asarray(jax.device_get(leaf))
    return out


def _unflatten_into(template, arrays: Dict[str, np.ndarray]):
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    new_leaves = []
    for path, leaf in leaves_with_paths:
        key = jax.tree_util.keystr(path)
        if key not in arrays:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = arrays[key]
        want = np.shape(leaf)
        if tuple(arr.shape) != tuple(want):
            raise ValueError(
                f"leaf {key}: checkpoint shape {arr.shape} != template {want}"
            )
        # the template gives structure, shape and dtype only: its buffers
        # may have been donated to a train step, so never read their values
        dtype = leaf.dtype if hasattr(leaf, "dtype") else np.asarray(leaf).dtype
        new_leaves.append(arr.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


MANIFEST_NAME = "MANIFEST.json"


def _shard_file(shard_id: int, num_shards: int) -> str:
    return f"shard-{shard_id:05d}-of-{num_shards:05d}"


class CheckpointManager:
    """Step-indexed checkpoints under ``directory/ckpt-%08d/`` with atomic
    rename, CRC verification, retention, and optional async writes.

    Two write layouts share one read path:

    * **single-writer** (:meth:`save`) — ``state.npz`` + ``meta.json``,
      committed by atomically renaming the whole step directory;
    * **sharded multi-writer** (:meth:`save_shard` + :meth:`commit`) — each
      elastic worker writes ``shard-%05d-of-%05d.npz`` (its slice of the
      sorted leaf names, round-robin) plus a CRC sidecar straight into the
      step directory, and the step becomes restorable only when a
      ``MANIFEST.json`` lands via atomic rename.  A crash that strands a
      manifest-less shard set, or a torn shard under a committed manifest
      (CRC mismatch), makes that step unrestorable and
      :meth:`restore_latest` walks back to the previous complete manifest —
      the multi-writer generalization of the Go pserver's CRC-checked shard
      checkpoints (go/pserver/service.go:244-303)."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        self.directory = directory
        self.max_to_keep = max_to_keep
        os.makedirs(directory, exist_ok=True)
        self._pending: Optional[threading.Thread] = None
        self._pending_error: Optional[BaseException] = None

    # -- write ----------------------------------------------------------
    def save(
        self,
        step: int,
        tree: Any,
        extra: Optional[Dict[str, Any]] = None,
        async_: bool = False,
    ) -> None:
        # Materialize on host *before* handing off so the training loop can
        # donate/overwrite device buffers immediately (orbax-style).
        arrays = _flatten(tree)
        self.wait()  # serialize with any in-flight async write
        if async_:

            def run():
                try:
                    self._write(step, arrays, extra)
                except BaseException as exc:  # surfaced by the next wait()
                    self._pending_error = exc

            # Non-daemon: interpreter exit joins it, so a checkpoint started
            # at the end of a script is never silently truncated.
            t = threading.Thread(target=run, name="paddle-ckpt-write",
                                 daemon=False)
            t.start()
            self._pending = t
        else:
            self._write(step, arrays, extra)

    def _write(self, step: int, arrays: Dict[str, np.ndarray], extra) -> None:
        final = os.path.join(self.directory, f"ckpt-{step:08d}")
        tmp = tempfile.mkdtemp(prefix=".tmp-ckpt-", dir=self.directory)
        try:
            data_path = os.path.join(tmp, "state.npz")
            np.savez(data_path, **arrays)
            crc = _crc_file(data_path)
            meta = {
                "step": step,
                "crc32": crc,
                "timestamp": time.time(),
                "n_leaves": len(arrays),
                "extra": extra or {},
            }
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(meta, f)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        from paddle_tpu.robustness import chaos as _chaos

        if _chaos.fire("torn_checkpoint"):
            # simulate a crash mid-write: the step dir exists, the data file
            # is truncated (restore must detect it and fall back)
            _chaos.tear_file(os.path.join(final, "state.npz"))
        self._retain()

    # -- sharded multi-writer plane (elastic scale-out) ------------------
    def save_shard(
        self,
        step: int,
        shard_id: int,
        num_shards: int,
        tree: Any,
        async_: bool = False,
    ) -> None:
        """Write THIS process's shard of the state pytree: the
        ``shard_id``-th slice of the sorted flattened leaf names, taken
        round-robin over ``num_shards``.  Host-materializes before handing
        off (the training loop may donate the device buffers immediately);
        ``async_=True`` runs the disk write off the hot path on a
        background thread — failures surface from :meth:`wait` and from the
        next ``save``/``save_shard``.  The step only becomes restorable
        once every shard landed and :meth:`commit` published the
        manifest."""
        # select THIS shard's leaves by key first, then device_get only
        # those: materializing the whole tree on every worker would pay N
        # full device-to-host transfers per checkpoint — the cost sharding
        # exists to avoid
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        keyed = {jax.tree_util.keystr(path): leaf for path, leaf in leaves}
        keys = sorted(keyed)[shard_id::num_shards]
        mine = {k: np.asarray(jax.device_get(keyed[k])) for k in keys}
        self.wait()  # serialize with (and surface) any in-flight write
        if async_:

            def run():
                try:
                    self._write_shard(step, shard_id, num_shards, mine)
                except BaseException as exc:  # surfaced by the next wait()
                    self._pending_error = exc

            t = threading.Thread(target=run, name="paddle-ckpt-shard",
                                 daemon=False)
            t.start()
            self._pending = t
        else:
            self._write_shard(step, shard_id, num_shards, mine)

    def _write_shard(
        self, step: int, shard_id: int, num_shards: int, arrays: Dict[str, np.ndarray]
    ) -> None:
        d = os.path.join(self.directory, f"ckpt-{step:08d}")
        os.makedirs(d, exist_ok=True)
        base = _shard_file(shard_id, num_shards)
        fd, tmp = tempfile.mkstemp(prefix=f".tmp-{base}-", dir=d)
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **arrays)
            crc = _crc_file(tmp)
            os.replace(tmp, os.path.join(d, base + ".npz"))
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        side = {"crc32": crc, "n_leaves": len(arrays)}
        side_tmp = os.path.join(d, "." + base + ".json.tmp")
        with open(side_tmp, "w") as f:
            json.dump(side, f)
        os.replace(side_tmp, os.path.join(d, base + ".json"))
        from paddle_tpu.robustness import chaos as _chaos

        if _chaos.fire("torn_checkpoint"):
            # crash-mid-write drill: the shard file is truncated AFTER its
            # CRC was recorded — a committed manifest must fail restore and
            # fall back to the previous complete one
            _chaos.tear_file(os.path.join(d, base + ".npz"))

    def commit(
        self,
        step: int,
        num_shards: int,
        extra: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """Publish a sharded step: verify every shard (and its CRC sidecar)
        landed, then atomically rename ``MANIFEST.json`` into place — the
        single commit point that makes the step restorable.  Idempotent
        (True if a manifest already exists) and safe to attempt from every
        worker: returns False — without committing — while any shard is
        missing (e.g. its writer died before the write finished)."""
        d = os.path.join(self.directory, f"ckpt-{step:08d}")
        man_path = os.path.join(d, MANIFEST_NAME)
        if os.path.exists(man_path):
            return True
        shards: Dict[str, int] = {}
        n_leaves = 0
        for i in range(num_shards):
            base = _shard_file(i, num_shards)
            side_path = os.path.join(d, base + ".json")
            if not os.path.exists(os.path.join(d, base + ".npz")):
                return False
            try:
                with open(side_path) as f:
                    side = json.load(f)
            except (OSError, ValueError):
                return False
            shards[base + ".npz"] = side["crc32"]
            n_leaves += side.get("n_leaves", 0)
        manifest = {
            "step": step,
            "num_shards": num_shards,
            "shards": shards,
            "n_leaves": n_leaves,
            "timestamp": time.time(),
            "extra": extra or {},
        }
        tmp = man_path + f".tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(manifest, f)
        os.replace(tmp, man_path)
        self._retain()
        return True

    def _retain(self) -> None:
        """Keep the newest ``max_to_keep`` COMMITTED steps.  Only committed
        steps count toward the quota and only steps OLDER than the oldest
        kept committed one are deleted: an uncommitted shard set that is
        still being written by other workers is always newer than the kept
        window and must never be reaped, while a stranded torn/uncommitted
        newest step must never push the last restorable manifest out."""
        committed = [s for s in self.all_steps() if self._is_committed(s)]
        if len(committed) <= self.max_to_keep:
            return
        keep_from = committed[-self.max_to_keep]
        for s in self.all_steps():
            if s < keep_from:
                shutil.rmtree(
                    os.path.join(self.directory, f"ckpt-{s:08d}"),
                    ignore_errors=True,
                )

    def _is_committed(self, step: int) -> bool:
        d = os.path.join(self.directory, f"ckpt-{step:08d}")
        return os.path.exists(os.path.join(d, "meta.json")) or os.path.exists(
            os.path.join(d, MANIFEST_NAME)
        )

    def wait(self) -> None:
        """Join any in-flight async write; re-raises its failure so a broken
        checkpoint never goes unnoticed."""
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._pending_error is not None:
            exc, self._pending_error = self._pending_error, None
            raise exc

    # -- read -----------------------------------------------------------
    def all_steps(self):
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("ckpt-"):
                try:
                    steps.append(int(name[5:]))
                except ValueError:
                    pass
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def meta(self, step: int) -> Dict[str, Any]:
        """The step's meta/manifest dict (meta.json for single-writer
        steps, MANIFEST.json for sharded ones)."""
        d = os.path.join(self.directory, f"ckpt-{step:08d}")
        for name in ("meta.json", MANIFEST_NAME):
            path = os.path.join(d, name)
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
        raise IOError(f"checkpoint {d}: no meta.json or {MANIFEST_NAME}")

    def restore(self, step: int, template: Any):
        """Verify CRC, then rebuild the pytree into `template`'s structure.
        Returns (tree, extra).  Sharded steps (MANIFEST.json) merge every
        shard, verifying each against its manifest CRC; an uncommitted
        shard set (no manifest) is unrestorable by definition."""
        d = os.path.join(self.directory, f"ckpt-{step:08d}")
        man_path = os.path.join(d, MANIFEST_NAME)
        if os.path.exists(man_path):
            with open(man_path) as f:
                manifest = json.load(f)
            arrays: Dict[str, np.ndarray] = {}
            for fname, crc in manifest["shards"].items():
                path = os.path.join(d, fname)
                if _crc_file(path) != crc:
                    raise IOError(
                        f"checkpoint shard {path} corrupt: crc mismatch vs "
                        f"manifest {crc:#x}"
                    )
                with np.load(path) as z:
                    arrays.update({k: z[k] for k in z.files})
            return _unflatten_into(template, arrays), manifest.get("extra", {})
        meta = self.meta(step)
        data_path = os.path.join(d, "state.npz")
        if _crc_file(data_path) != meta["crc32"]:
            raise IOError(
                f"checkpoint {d} corrupt: crc mismatch vs meta {meta['crc32']:#x}"
            )
        with np.load(data_path) as z:
            arrays = {k: z[k] for k in z.files}
        return _unflatten_into(template, arrays), meta.get("extra", {})

    def restore_latest(self, template: Any):
        """Newest RESTORABLE checkpoint as ``(step, tree, extra)`` — or None
        when the directory holds none that loads.

        Unlike :meth:`restore` (strict: a caller naming a step deserves the
        error), this walks newest → oldest past torn/corrupt step dirs: a
        truncated ``state.npz`` (crash mid-write), a CRC mismatch (bit rot),
        or a missing ``meta.json`` must never brick a resume while an older
        retained checkpoint is intact — the Go pserver's checkpoint loader
        takes the same stance (service.go:244: a bad CRC fails over rather
        than wedging the shard)."""
        import logging

        log = logging.getLogger("paddle_tpu.checkpoint")
        for step in reversed(self.all_steps()):
            try:
                tree, extra = self.restore(step, template)
            except Exception as exc:  # noqa: BLE001 — any torn artifact
                log.warning(
                    "checkpoint ckpt-%08d unusable (%s: %s); falling back "
                    "to the previous retained checkpoint",
                    step, type(exc).__name__, exc,
                )
                continue
            return step, tree, extra
        return None
