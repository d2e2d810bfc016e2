"""Persistent AOT executable cache — warm boot = deserialize, not retrace.

The in-process :class:`~paddle_tpu.core.compiler.CompileShapeCache` accounts
jit keys per batch-shape rung; this module extends that contract onto disk:
every (step kind, topology, ladder rung, mesh, dtype/donation) variant the
shape ladder realizes is serialized once — ``jit(...).lower(...).compile()``
+ ``jax.experimental.serialize_executable`` — and every later process boot
deserializes instead of paying the full XLA retrace.  This is the
Julia-to-TPU paper's full-compilation argument (arXiv:1810.09868) applied to
boot cost: the whole train step is ONE offloadable XLA computation, so its
compiled form is a cacheable artifact, multiplied across the bucketing
ladder's rung set and across every worker of a fleet.

Safety model — a wrong executable must be impossible to load:

* **Identity key** (hashed into the filename): step kind + n_steps,
  topology fingerprint (``Topology.serialize()`` hash + compute dtype),
  batch shape-ladder key, mesh/sharding spec, donation signature.
* **Environment key** (stored in the entry header, compared on load):
  jax version, backend platform, device kind + count, optimizer
  fingerprint, package version.  A mismatch is a **stale** entry — counted,
  warned once, retraced, and overwritten with a fresh entry.  An entry
  whose header names a different identity (hash collision, a foreign file
  renamed into place) is detected the same way: the FULL key is compared,
  never trusted from the filename.
* **Integrity**: the pickled executable blob carries a CRC32 and its byte
  length in the header; truncation or corruption is a **corrupt** entry —
  counted, warned once, retraced, overwritten.  Loads never raise.
* **Devices**: the header records the ids of the devices the program was
  compiled for, in assignment order, and the load hands exactly those to
  ``deserialize_and_load(execution_devices=...)`` — without them jax
  spreads the loaded program over every local device, so a one-device
  executable fails on a host with several.  An id this process cannot
  see is a **stale** entry.

Counters ride the StatSet plane (``aot_cache/{hit,miss,stale,corrupt}``) so
the per-pass stats table says whether a boot was warm.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import struct
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

_log = logging.getLogger("paddle_tpu.aot_cache")

__all__ = [
    "AOTCache",
    "optimizer_fingerprint",
    "topology_fingerprint",
    "mesh_fingerprint",
]

_MAGIC = b"PTAOT1\n"
_SUFFIX = ".aotx"


def topology_fingerprint(network) -> str:
    """Identity of the compiled program's graph: the serialized topology
    (types/sizes/attrs — the same structural comparison SGD uses to decide
    network reuse) plus the compute dtype it lowers at."""
    text = network.topology.serialize() + f"|compute={network.compute_dtype}"
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def mesh_fingerprint(mesh) -> str:
    if mesh is None:
        return "none"
    try:
        shape = dict(mesh.shape)
    except Exception:
        shape = {}
    return f"axes={sorted(shape.items())}"


def optimizer_fingerprint(opt) -> str:
    """Stable identity of an optimizer's baked-in constants (learning rate,
    schedule args, slot hyperparameters): two optimizers that would compile
    different update programs must fingerprint differently — an executable
    cached for lr=0.1 silently reused at lr=0.01 is exactly the wrong-code
    load this cache must never do."""
    parts: Dict[str, Any] = {"class": type(opt).__name__}
    for k, v in sorted(vars(opt).items()):
        if isinstance(v, (int, float, str, bool, tuple, type(None))):
            parts[k] = v
        elif k in ("regularization", "model_average"):
            parts[k] = repr(v)
    return repr(sorted(parts.items()))


def _env_key() -> Dict[str, Any]:
    import jax

    try:
        devs = jax.devices()
        kind, count = devs[0].device_kind, len(devs)
        platform = devs[0].platform
    except Exception:  # pragma: no cover - backendless build
        kind, count, platform = "unknown", 0, "unknown"
    import paddle_tpu

    return {
        "jax": jax.__version__,
        "backend": platform,
        "device_kind": kind,
        "n_devices": count,
        "paddle_tpu": paddle_tpu.__version__,
    }


def _key_hash(identity: Dict[str, Any]) -> str:
    return hashlib.sha256(
        json.dumps(identity, sort_keys=True).encode()
    ).hexdigest()[:24]


def _write_entry(path: str, header: Dict[str, Any], blob: bytes) -> None:
    """MAGIC | header_len:u32 | header json | crc32:u32 | blob — written
    tmp+rename so a concurrent reader never sees a torn entry."""
    hdr = json.dumps(header, sort_keys=True).encode()
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack(">I", len(hdr)))
        f.write(hdr)
        f.write(struct.pack(">I", zlib.crc32(blob) & 0xFFFFFFFF))
        f.write(blob)
    os.replace(tmp, path)


def _read_header(path: str) -> Tuple[Dict[str, Any], int, int]:
    """(header, blob offset, blob crc) — framing-validated WITHOUT reading
    the blob (``cache ls`` lists hundreds of MB of executables by header
    alone).  Raises ValueError on any damage, including truncation inside
    the fixed-size fields: every read is length-checked before unpacking,
    so a torn file can never leak a struct.error past the caller's
    ValueError handling."""
    with open(path, "rb") as f:
        if f.read(len(_MAGIC)) != _MAGIC:
            raise ValueError("bad magic")
        raw = f.read(4)
        if len(raw) != 4:
            raise ValueError("truncated header length")
        (hlen,) = struct.unpack(">I", raw)
        hraw = f.read(hlen)
        if len(hraw) != hlen:
            raise ValueError(f"truncated header: {len(hraw)} != {hlen} bytes")
        try:
            header = json.loads(hraw.decode())
        except Exception as e:
            raise ValueError(f"bad header: {e}")
        raw = f.read(4)
        if len(raw) != 4:
            raise ValueError("truncated CRC")
        (crc,) = struct.unpack(">I", raw)
    return header, len(_MAGIC) + 4 + hlen + 4, crc


def _read_entry(path: str) -> Tuple[Dict[str, Any], bytes]:
    """(header, blob) — raises ValueError on any framing/CRC damage (the
    caller maps that to the `corrupt` counter; this never loads a damaged
    blob)."""
    header, offset, crc = _read_header(path)
    with open(path, "rb") as f:
        f.seek(offset)
        blob = f.read()
    want = int(header.get("blob_bytes", -1))
    if want >= 0 and len(blob) != want:
        raise ValueError(f"truncated blob: {len(blob)} != {want} bytes")
    if zlib.crc32(blob) & 0xFFFFFFFF != crc:
        raise ValueError("blob CRC mismatch")
    return header, blob


class AOTCache:
    """On-disk serialized-executable store keyed by the ladder contract.

    ``get_or_compile(jitted, args, identity, meta)`` is the whole surface a
    dispatch loop needs: a valid entry deserializes (**hit**), anything
    else compiles via ``jitted.lower(*args).compile()`` (**miss**; stale /
    corrupt entries also bump their own counter) and — when this jax can
    serialize — writes the fresh executable back for the next boot.

    ``identity`` names what program this is (hashed into the filename);
    ``meta`` names what must ALSO match for the entry to be loadable
    (jax/backend versions are merged in automatically).  ``compiles`` and
    ``loads`` count what actually happened in-process — the warm-boot
    assertion (`compiles == 0` on a populated cache) reads them directly.
    """

    def __init__(self, cache_dir: str, stats=None):
        from paddle_tpu.utils.timers import global_stats

        self.dir = os.path.abspath(cache_dir)
        os.makedirs(self.dir, exist_ok=True)
        self._stats = stats if stats is not None else global_stats
        self.compiles = 0  # full XLA compiles this process performed
        self.loads = 0  # executables deserialized from disk
        self._warned: set = set()

    # -- key plumbing ----------------------------------------------------
    def entry_path(self, identity: Dict[str, Any]) -> str:
        return os.path.join(self.dir, _key_hash(identity) + _SUFFIX)

    def full_key(self, identity: Dict[str, Any], meta: Optional[Dict] = None
                 ) -> Dict[str, Any]:
        return {**identity, **(meta or {}), **_env_key()}

    def _warn_once(self, category: str, msg: str, *args) -> None:
        if category not in self._warned:
            self._warned.add(category)
            _log.warning(msg + " (warning once; counters keep counting)",
                         *args)

    # -- load / store ----------------------------------------------------
    def load(self, identity: Dict[str, Any], meta: Optional[Dict] = None):
        """The cached executable for this full key, or None (miss / stale /
        corrupt — counted; never raises, never loads a mismatched entry)."""
        path = self.entry_path(identity)
        if not os.path.exists(path):
            return None
        try:
            header, blob = _read_entry(path)
        except (OSError, ValueError) as e:
            self._stats.incr("aot_cache/corrupt")
            self._warn_once(
                "corrupt",
                "aot cache entry %s is damaged (%s); retracing", path, e,
            )
            return None
        want = self.full_key(identity, meta)
        have = header.get("key", {})
        if have != want:
            diff = sorted(
                k for k in set(want) | set(have)
                if want.get(k) != have.get(k)
            )
            self._stats.incr("aot_cache/stale")
            self._warn_once(
                "stale",
                "aot cache entry %s is stale (mismatched fields: %s); "
                "retracing", path, diff,
            )
            return None
        import jax

        by_id = {d.id: d for d in jax.devices()}
        ids = header.get("device_ids")
        if not ids or any(i not in by_id for i in ids):
            self._stats.incr("aot_cache/stale")
            self._warn_once(
                "stale",
                "aot cache entry %s was compiled for device ids %s, which "
                "this process does not have; retracing", path, ids,
            )
            return None
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = pickle.loads(blob)  # wire: allow[A206] local CRC32-verified AOT cache blob under the operator's cache_dir, never network input; serialized XLA executables are not expressible in the restricted wire codec
            exe = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in ids],
            )
        except Exception as e:
            self._stats.incr("aot_cache/corrupt")
            self._warn_once(
                "corrupt",
                "aot cache entry %s failed to deserialize (%s); retracing",
                path, e,
            )
            return None
        self._stats.incr("aot_cache/hit")
        self.loads += 1
        return exe

    def store(self, identity: Dict[str, Any], compiled,
              meta: Optional[Dict] = None) -> bool:
        """Serialize one compiled executable; False (warn once, counted
        as ``aot_cache/unsupported``) when the backend refuses to serialize
        it — the retrace path stays correct."""
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree))
        except Exception as e:
            self._stats.incr("aot_cache/unsupported")
            self._warn_once(
                "unsupported",
                "executable refused to serialize (%s); aot cache entry "
                "skipped", e,
            )
            return False
        header = {
            "key": self.full_key(identity, meta),
            "created": time.time(),
            "blob_bytes": len(blob),
            "device_ids": [
                d.id for d in compiled.runtime_executable().local_devices()
            ],
        }
        try:
            _write_entry(self.entry_path(identity), header, blob)
        except OSError as e:
            self._warn_once(
                "unwritable", "aot cache dir %s unwritable (%s)", self.dir, e
            )
            return False
        return True

    def get_or_compile(self, jitted, args, identity: Dict[str, Any],
                       meta: Optional[Dict] = None):
        """One dispatch-boundary call: cached executable when the full key
        matches, else compile (counted as a miss — the warm-boot metric is
        exactly these), store for the next boot, and return the compiled
        executable so the caller never pays the trace twice."""
        exe = self.load(identity, meta)
        if exe is not None:
            return exe
        self._stats.incr("aot_cache/miss")
        compiled = jitted.lower(*args).compile()
        self.compiles += 1
        self.store(identity, compiled, meta)
        return compiled

    # -- maintenance (the `paddle-tpu cache` CLI surface) ----------------
    def entries(self) -> List[Dict[str, Any]]:
        """Per-entry metadata for ``cache ls``: size, age, and the full key
        provenance out of the header (damaged headers list as corrupt).
        Header-only reads — blob integrity is the load path's job, so
        listing a store of hundreds of MB stays cheap."""
        out: List[Dict[str, Any]] = []
        for name in sorted(os.listdir(self.dir)):
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.dir, name)
            ent: Dict[str, Any] = {
                "file": name,
                "bytes": os.path.getsize(path),
                "mtime": os.path.getmtime(path),
            }
            try:
                header, _, _ = _read_header(path)
                ent["key"] = header.get("key", {})
                ent["created"] = header.get("created")
            except (OSError, ValueError) as e:
                ent["corrupt"] = str(e)
            out.append(ent)
        return out

    def total_bytes(self) -> int:
        return sum(e["bytes"] for e in self.entries())

    def _sweep_tmp(self) -> List[str]:
        """Remove orphaned ``*.tmp.<pid>`` files a killed writer left
        behind (the chaos/preemption drills SIGKILL mid-write by design).
        Only run from the explicit maintenance commands — a tmp file
        belonging to a LIVE concurrent writer swept at boot would fail its
        rename."""
        removed = []
        for name in os.listdir(self.dir):
            if ".tmp." not in name:
                continue
            try:
                os.remove(os.path.join(self.dir, name))
                removed.append(name)
            except OSError:
                pass
        return removed

    def prune(self, max_bytes: int) -> List[str]:
        """Drop oldest-first (mtime) until the store fits; orphaned tmp
        files and corrupt entries go first.  Returns the removed
        filenames."""
        removed_tmp = self._sweep_tmp()
        ents = self.entries()
        ents.sort(key=lambda e: (0 if "corrupt" in e else 1, e["mtime"]))
        total = sum(e["bytes"] for e in ents)
        removed = list(removed_tmp)
        for e in ents:
            if total <= max_bytes and "corrupt" not in e:
                break
            try:
                os.remove(os.path.join(self.dir, e["file"]))
            except OSError:
                continue
            total -= e["bytes"]
            removed.append(e["file"])
        return removed

    def clear(self) -> int:
        n = len(self._sweep_tmp())
        for name in os.listdir(self.dir):
            if name.endswith(_SUFFIX):
                try:
                    os.remove(os.path.join(self.dir, name))
                    n += 1
                except OSError:
                    pass
        return n

    def summary(self) -> Dict[str, Any]:
        ents = self.entries()  # one directory scan, header-only reads
        return {
            "dir": self.dir,
            "entries": len(ents),
            "mb": round(sum(e["bytes"] for e in ents) / 1e6, 2),
            "compiles": self.compiles,
            "loads": self.loads,
            "hit": self._stats.count("aot_cache/hit"),
            "miss": self._stats.count("aot_cache/miss"),
            "stale": self._stats.count("aot_cache/stale"),
            "corrupt": self._stats.count("aot_cache/corrupt"),
            "unsupported": self._stats.count("aot_cache/unsupported"),
        }
