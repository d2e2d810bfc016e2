"""From a profiler trace (.xplane.pb) to numbers.  Reads the file with jax's
own ProfileData for the timelines, and with a small reader of the protobuf
wire format for what ProfileData does not expose: the per-operation metadata
of a device plane (`tf_op`, the jax name stack with the program's
`named_scope("type:name")` of each layer, and `hlo_category`).

What the trace of this runtime looks like (probe, PR 24): one plane
`/device:TPU:<n>` per chip with the lines `XLA Modules` (one event per
program run), `XLA Ops` (one event per HLO operation run, named by the HLO
instruction's text; a `while` or `conditional` is an event that contains its
body's events) and `Async XLA Ops`; one plane `/host:CPU` with a line per
thread, where the program's obs spans appear under their own names.
"""

import glob
import os
import re

CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|collective-broadcast")
SCOPE = re.compile(r"([A-Za-z_0-9]+:[A-Za-z_0-9.@]+)")


# -- the wire format ---------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start=0, end=None):
    """Yields (field number, wire type, value): an int for varints and fixed
    widths, a (start, end) span of `buf` for length-delimited fields."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    for num, _, v in _fields(buf, *span):
        if num == 2:
            return v
    return None


def _stat(buf, span, stat_names):
    name = value = None
    for num, wt, v in _fields(buf, *span):
        if num == 1:
            name = stat_names.get(v)
        elif num == 5:
            value = _text(buf, v)
        elif num in (3, 4):
            value = v
        elif num == 7:
            value = stat_names.get(v)
    return name, value


def operation_metadata(path):
    """{plane name: {operation's event name: {"tf_op", "hlo_category"}}} for
    the device planes of an .xplane.pb file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, _, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_spans = "", [], []
        for pnum, _, v in _fields(buf, *plane):
            if pnum == 2:
                name = _text(buf, v)
            elif pnum == 4:
                events.append(v)
            elif pnum == 5:
                stat_spans.append(v)
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for span in stat_spans:
            val = _map_value(buf, span)
            sid = sname = None
            for snum, _, v in _fields(buf, *val):
                if snum == 1:
                    sid = v
                elif snum == 2:
                    sname = _text(buf, v)
            stat_names[sid] = sname
        ops = {}
        for span in events:
            val = _map_value(buf, span)
            ename, meta = "", {}
            for enum, _, v in _fields(buf, *val):
                if enum == 2:
                    ename = _text(buf, v)
                elif enum == 5:
                    k, sv = _stat(buf, v, stat_names)
                    if k in ("tf_op", "hlo_category"):
                        meta[k] = sv
            ops[ename] = meta
        out[name] = ops
    return out


# -- the timelines -----------------------------------------------------------

def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_seconds(intervals):
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The idle stretches [(start, end)] inside [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def scopes_of(tf_op):
    """The program's layer scopes ("type:name") in a jax name stack."""
    return SCOPE.findall(tf_op or "")


class Trace:
    """A trace, reduced to what the layer metrics read.

    devices: {plane: {"ops": [(start_s, end_s, name)], "modules": [(start_s, end_s, name)]}}
        ops are the leaf operations of the `XLA Ops` line (containers left out)
    meta: {plane: {name: {"tf_op", "hlo_category"}}}
    host: {span name: [(start_s, end_s)]} over all host threads
    """

    def __init__(self, devices, meta, host):
        self.devices, self.meta, self.host = devices, meta, host

    @classmethod
    def from_file(cls, path, module="jit_step"):
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        meta = operation_metadata(path)
        devices, host = {}, {}
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                ops, modules = [], []
                pmeta = meta.get(plane.name, {})
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for e in line.events:
                            cat = pmeta.get(e.name, {}).get("hlo_category", "")
                            if cat in CONTAINERS:
                                continue
                            s = e.start_ns * 1e-9
                            ops.append((s, s + e.duration_ns * 1e-9, e.name))
                    elif line.name == "XLA Modules":
                        for e in line.events:
                            if e.name.startswith(module):
                                s = e.start_ns * 1e-9
                                modules.append((s, s + e.duration_ns * 1e-9, e.name))
                devices[plane.name] = {"ops": ops, "modules": modules}
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if not e.name.startswith("$"):  # python frames, where traced
                            s = e.start_ns * 1e-9
                            host.setdefault(e.name, []).append((s, s + e.duration_ns * 1e-9))
        return cls(devices, meta, host)

    # the traced window: from the start of the first whole step program to
    # the end of the last, on each device
    def window(self, plane):
        mods = self.devices[plane]["modules"]
        if not mods:
            return None
        return min(m[0] for m in mods), max(m[1] for m in mods)

    def steps(self, plane):
        return len(self.devices[plane]["modules"])

    def busy_seconds(self, plane):
        lo, hi = self.window(plane)
        iv = [(max(s, lo), min(e, hi)) for s, e, _ in self.devices[plane]["ops"]
              if e > lo and s < hi]
        return union_seconds(iv)

    def seconds_where(self, plane, pred):
        """Device seconds of the leaf operations inside the window whose
        metadata satisfies pred(name, tf_op, hlo_category)."""
        lo, hi = self.window(plane)
        pmeta = self.meta.get(plane, {})
        total = 0.0
        for s, e, name in self.devices[plane]["ops"]:
            if e <= lo or s >= hi:
                continue
            m = pmeta.get(name, {})
            if pred(name, m.get("tf_op", ""), m.get("hlo_category", "")):
                total += min(e, hi) - max(s, lo)
        return total

    def fullest(self):
        """The device plane with the most busy time."""
        planes = [p for p in self.devices if self.devices[p]["modules"]]
        return max(planes, key=self.busy_seconds)

    def top_operations(self, plane, n=10):
        """[(label, seconds)]: device time by the innermost layer scope and
        the kind of operation."""
        lo, hi = self.window(plane)
        pmeta = self.meta.get(plane, {})
        acc = {}
        for s, e, name in self.devices[plane]["ops"]:
            if e <= lo or s >= hi:
                continue
            m = pmeta.get(name, {})
            sc = scopes_of(m.get("tf_op", ""))
            tf_op = m.get("tf_op", "")
            way = "bwd" if "transpose(" in tf_op else "fwd"
            label = f"{sc[-1] if sc else 'no-layer-scope'} {way} {m.get('hlo_category', '?')}"
            acc[label] = acc.get(label, 0.0) + (min(e, hi) - max(s, lo))
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, plane, n=10):
        """[(label, seconds)]: the longest idle stretches of the device, each
        labelled with what the host was doing: the span of the trainer's
        thread (`feed_wait`, `train_step`, `block_fetch`: between them they
        cover its `step`) that covers most of the stretch; where none of them
        touches it, `feed`, the prefetch thread's, if that does."""
        lo, hi = self.window(plane)
        iv = [(s, e) for s, e, _ in self.devices[plane]["ops"]]

        def most_cover(s, e, names):
            cover = {k: sum(max(0.0, min(e, b) - max(s, a)) for a, b in self.host.get(k, ()))
                     for k in names}
            best = max(cover, key=cover.get)
            return best if cover[best] > 0.0 else None

        acc = {}
        for s, e in gaps(iv, lo, hi):
            label = (most_cover(s, e, ("feed_wait", "train_step", "block_fetch"))
                     or most_cover(s, e, ("feed",)) or "no-span")
            acc[label] = acc.get(label, 0.0) + (e - s)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]
