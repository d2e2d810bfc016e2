"""Parameters — the ``paddle.v2.parameters`` surface (reference:
python/paddle/v2/parameters.py) plus reference-compatible tar checkpoints.

The tar layout matches the reference so v1/v2 checkpoints interoperate:
one member per parameter whose payload is the v1 binary header
(int32 version=0, uint32 value_size=4, uint64 num_elements) followed by raw
float32 data (reference: paddle/parameter/Parameter.cpp save/load:~250-340,
python/paddle/v2/parameters.py to_tar/from_tar).
"""

from __future__ import annotations

import io
import struct
import tarfile
from typing import Dict, Iterator, Optional, Tuple

import jax
import numpy as np

from paddle_tpu import obs as _obs
from paddle_tpu.core.compiler import CompiledNetwork, NetState, Params
from paddle_tpu.core.topology import Topology


class Parameters:
    """Holds the parameter pytree + non-trainable state for a topology."""

    def __init__(self, network: CompiledNetwork, params: Params, state: NetState):
        self.network = network
        self.params = params
        self.state = state

    # -- dict-like numpy access (name = dotted path, e.g. "fc0.w0" or
    # "decoder.hproj.w0" for nested recurrent_group params) --------------
    def names(self):
        out = []

        def walk(prefix, node):
            if isinstance(node, dict):
                for k in node:
                    walk(f"{prefix}.{k}" if prefix else k, node[k])
            else:
                out.append(prefix)

        walk("", self.params)
        return out

    def keys(self):
        return self.names()

    def _resolve(self, key: str):
        parts = key.split(".")
        node = self.params
        try:
            for p in parts[:-1]:
                node = node[p]
            if parts[-1] not in node:
                raise KeyError(parts[-1])
        except (KeyError, TypeError):
            # fall back to the GLOBAL parameter name table (reference
            # parameters are named objects: parameters.get("embedding.w0"))
            named = getattr(self.network, "named_parameters", None)
            if named is not None and key in (table := named()):
                node, leaf = self._resolve(table[key])
                # legacy whole-layer names address the layer's param DICT;
                # descend to its single leaf (reference one-parameter
                # layers), never hand back a dict as if it were an array
                while isinstance(node[leaf], dict):
                    inner = node[leaf]
                    if len(inner) != 1:
                        raise KeyError(
                            f"named parameter {key!r} maps to a multi-key "
                            f"param dict ({sorted(inner)}); address a leaf "
                            f"as {table[key]}.<key>"
                        )
                    node, leaf = inner, next(iter(inner))
                return node, leaf
            raise
        return node, parts[-1]

    def get(self, key: str) -> np.ndarray:
        node, leaf = self._resolve(key)
        return np.asarray(node[leaf])

    __getitem__ = get

    def set(self, key: str, value: np.ndarray) -> None:
        import jax.numpy as jnp

        node, leaf = self._resolve(key)
        old = node[leaf]
        value = jnp.asarray(value, dtype=old.dtype).reshape(old.shape)
        node[leaf] = value

    __setitem__ = set

    def __iter__(self) -> Iterator[str]:
        return iter(self.names())

    def __len__(self) -> int:
        return len(self.names())

    # -- tar checkpoints ------------------------------------------------
    def to_tar(self, f) -> None:
        """Reference v2 tar layout (python/paddle/v2/parameters.py:266):
        per parameter a data member (v1 binary header + raw float32) AND a
        ``<name>.protobuf`` ParameterConfig member carrying name/size/dims
        (hand-rolled proto2 wire bytes — fields 1, 2, 9 of
        proto/ParameterConfig.proto) so the static ``from_tar`` can
        restore shapes, and the reference itself can parse the file."""
        with tarfile.open(fileobj=f, mode="w") as tar:
            for name in self.names():
                _write_tar_member(tar, name, self.get(name))

    def init_from_tar(self, f) -> None:
        """Merge a parameter tar into THIS instance, ignoring names the
        topology doesn't have (reference Parameters.init_from_tar,
        python/paddle/v2/parameters.py:314)."""
        known = set(self.names())
        for name, arr in _read_tar_members(f):
            if name in known:
                self.set(name, arr)

    class _FromTar:
        """``Parameters.from_tar(f)`` on the CLASS is the reference's
        static constructor (python/paddle/v2/parameters.py:286) and
        returns a topology-free :class:`DetachedParameters`; on an
        INSTANCE it merges into the existing parameters (kept as an
        alias of :meth:`init_from_tar` for the library's own callers)."""

        def __get__(self, obj, objtype=None):
            if obj is None:
                return DetachedParameters.from_tar
            return obj.init_from_tar

    from_tar = _FromTar()

    @staticmethod
    def from_tar_new(network: CompiledNetwork, f) -> "Parameters":
        p = create_from_network(network, seed=0)
        p.init_from_tar(f)
        return p


def _write_tar_member(tar, name: str, arr: np.ndarray) -> None:
    """One parameter as the reference pair of members: v1-binary data +
    ParameterConfig shape record."""
    arr = np.asarray(arr, np.float32)
    payload = struct.pack("<iIQ", 0, 4, arr.size) + arr.tobytes()
    info = tarfile.TarInfo(name=name)
    info.size = len(payload)
    tar.addfile(info, io.BytesIO(payload))
    conf = _encode_param_conf(name, arr.shape)
    cinfo = tarfile.TarInfo(name=f"{name}.protobuf")
    cinfo.size = len(conf)
    tar.addfile(cinfo, io.BytesIO(conf))


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _encode_param_conf(name: str, shape) -> bytes:
    """Minimal proto2 ParameterConfig wire bytes: name (field 1, string),
    size (field 2, uint64), dims (field 9, repeated uint64)."""
    nb = name.encode("utf-8")
    out = b"\x0a" + _varint(len(nb)) + nb  # field 1, wire type 2
    size = 1
    for d in shape:
        size *= int(d)
    out += b"\x10" + _varint(size)  # field 2, wire type 0
    for d in shape:
        out += b"\x48" + _varint(int(d))  # field 9, wire type 0
    return out


def _parse_param_conf(buf: bytes, member: str = "?"):
    """Parse the fields we wrote (skipping any others a reference-written
    tar may carry).  Returns (name, dims)."""
    name, dims = None, []
    i, n = 0, len(buf)

    def read_varint(i):
        v, shift = 0, 0
        while True:
            if i >= n:
                raise ValueError(
                    f"corrupt ParameterConfig member {member!r}: varint "
                    f"runs past the end of the {n}-byte record"
                )
            b = buf[i]
            v |= (b & 0x7F) << shift
            i += 1
            if not b & 0x80:
                return v, i
            shift += 7

    while i < n:
        tag, i = read_varint(i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = read_varint(i)
            if field == 9:
                dims.append(v)
        elif wire == 1:
            i += 8
        elif wire == 2:
            ln, i = read_varint(i)
            if field == 1:
                name = buf[i : i + ln].decode("utf-8")
            i += ln
        elif wire == 5:
            i += 4
        else:
            # wire types 3/4 (proto2 groups) and 6/7 don't appear in any
            # ParameterConfig a reference build can write; a partial parse
            # here would silently load the array flat (shapeless), so fail
            # loudly like the varint-overrun path does
            raise ValueError(
                f"corrupt ParameterConfig member {member!r}: unknown proto "
                f"wire type {wire} (field {field}) at byte {i}"
            )
    return name, dims


def _read_tar_members(f):
    """Yield (name, float32 array) for each data member of a
    reference-format parameter tar, with shapes restored from any
    ``<name>.protobuf`` ParameterConfig members present."""
    with tarfile.open(fileobj=f, mode="r") as tar:
        members = tar.getmembers()
        dims = {}
        for member in members:
            if member.name.endswith(".protobuf"):
                nm, dd = _parse_param_conf(
                    tar.extractfile(member).read(), member.name
                )
                dims[nm if nm else member.name[: -len(".protobuf")]] = dd
        for member in members:
            if member.name.endswith(".protobuf"):
                continue
            buf = tar.extractfile(member).read()
            version, value_size, size = struct.unpack("<iIQ", buf[:16])
            assert value_size == 4, "only float32 checkpoints supported"
            arr = np.frombuffer(buf[16 : 16 + 4 * size], dtype=np.float32)
            dd = dims.get(member.name)
            if dd and int(np.prod(dd)) == arr.size:
                arr = arr.reshape([int(d) for d in dd])
            yield member.name, arr


class DetachedParameters:
    """Topology-free parameter bag — what the reference's static
    ``Parameters.from_tar(f)`` returns: names + float32 values with no
    network attached.  Accepted anywhere a Parameters is (SGD, Inference,
    infer): the consumer builds its own parameters from the topology and
    merges these values in by name."""

    def __init__(self, values: Dict[str, np.ndarray]):
        self._values = dict(values)

    @staticmethod
    def from_tar(f) -> "DetachedParameters":
        if isinstance(f, Parameters) or not hasattr(f, "read"):
            # the class/instance duality of Parameters.from_tar (_FromTar):
            # an unbound-style call Parameters.from_tar(params_obj, f) lands
            # here with the Parameters object as `f` — catch it before
            # tarfile produces an opaque error
            raise TypeError(
                "Parameters.from_tar on the CLASS is the static constructor "
                "taking a single binary file object (got "
                f"{type(f).__name__}); to merge a tar into an existing "
                "Parameters call params.from_tar(f) / params.init_from_tar(f)"
            )
        return DetachedParameters(dict(_read_tar_members(f)))

    def names(self):
        return list(self._values)

    keys = names

    def get(self, key: str) -> np.ndarray:
        return self._values[key]

    __getitem__ = get

    def set(self, key: str, value: np.ndarray) -> None:
        self._values[key] = np.asarray(value)

    __setitem__ = set

    def __iter__(self) -> Iterator[str]:
        return iter(self._values)

    def __len__(self) -> int:
        return len(self._values)

    def __contains__(self, key: str) -> bool:
        return key in self._values

    def to_tar(self, f) -> None:
        with tarfile.open(fileobj=f, mode="w") as tar:
            for name, arr in self._values.items():
                _write_tar_member(tar, name, arr)

    def merge_into(self, parameters: Parameters) -> Parameters:
        """Copy every name the target topology knows into `parameters`.
        Warns when NOTHING matches — that means the tar came from a
        different/renamed topology and the consumer would otherwise run on
        silently random weights."""
        known = set(parameters.names())
        hit = [n for n in self._values if n in known]
        if self._values and not hit:
            import warnings

            warnings.warn(
                "parameter tar matched no parameter names of the target "
                f"topology (tar has {sorted(self._values)[:5]}..., topology "
                f"has {sorted(known)[:5]}...); the model keeps its random "
                "initialization",
                stacklevel=2,
            )
        elif (uncovered := sorted(known - set(self._values))):
            import warnings

            warnings.warn(
                f"parameter tar covers {len(hit)} of {len(known)} topology "
                f"parameters; {uncovered[:8]} keep their random "
                "initialization (use init_from_tar directly for intentional "
                "partial loads)",
                stacklevel=2,
            )
        for name in hit:
            parameters.set(name, self._values[name])
        return parameters


def create(cost_or_topology, seed: int = 0, dtype=None) -> Parameters:
    """paddle.parameters.create(cost) equivalent."""
    # obs: the start-up layer's span over the graph's compile and the
    # initial values (one eager program per distinct initializer shape)
    with _obs.span("parameters_create", cat="setup"):
        if isinstance(cost_or_topology, Topology):
            topo = cost_or_topology
        else:
            topo = Topology(cost_or_topology)
        network = CompiledNetwork(topo, dtype=dtype) if dtype else CompiledNetwork(topo)
        return _init_values(network, seed)


def create_from_network(network: CompiledNetwork, seed: int = 0) -> Parameters:
    with _obs.span("parameters_create", cat="setup"):
        return _init_values(network, seed)


def _init_values(network: CompiledNetwork, seed: int) -> Parameters:
    rng = jax.random.PRNGKey(seed)
    params, state = network.init(rng)
    return Parameters(network, params, state)
