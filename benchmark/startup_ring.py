"""Where set-up went, by the program's own account: the six `startup_*`
per-layer metrics (layer start-up, all moving `setup_s`) are read off the
span ring of `paddle_tpu.obs.tracer`, in the program's own process, after the
window and the reference.

The ring of the calling (main) thread holds, from `import paddle_tpu` on:
the start-up layer's spans (cat `setup`: import, init, parameters_create,
trainer_build > compile_network, make_train_step, make_eval_step,
optimizer_init; train_prepare), one `train` span (cat `trainer`) a call of
`trainer.train`, and one complete event (phase X, cat `jit`) for every
program jax traced (`jit_trace`), lowered (`jit_lower`) and compiled or
loaded (`jit_compile`, with `cache` = hit | miss | off).

What counts.  Only what begins before the LAST `train` begins: run.py calls
`trainer.train` for the checked steps, then once for the window, and then the
reference compiles programs of its own through the same jax.  Of the `jit_*`
intervals only those inside one of the program's own spans (OWN): the draw of
the weights and the norms of the first gradient are the harness's programs,
compiled between the program's spans.  Intervals of one kind are summed as
their union (an inner jitted function is traced inside its caller's trace),
and a cache load inside a trace belongs to the compiles alone, so the four
times are disjoint and their sum is no more than `setup_s`.

-> None (the metric is left out of the line) where the ring holds no `setup`
span (a program that has none), or has dropped events since `import`: a
truncated set-up must never be read as a short one.
"""

import threading

import trace_reduce

OWN = ("init", "parameters_create", "trainer_build", "train")
BUILD = ("init", "parameters_create", "trainer_build", "train_prepare")


def program_ring():
    """-> (the calling thread's events, oldest first; how many that ring has
    dropped), or None where the program keeps no such count."""
    from paddle_tpu.obs import tracer

    evicted = getattr(tracer, "evicted", None)
    if evicted is None:
        return None
    tid = threading.get_ident()
    return [e for e in tracer.events() if e.get("tid") == tid and e["ph"] != "M"], evicted(tid)


def intervals(events):
    """-> [(name, cat, start_s, end_s, args)] from one thread's B/E pairs and
    X events, in order of their ends' arrival; a B left open is left out."""
    out, stack = [], []
    for e in events:
        t = e["ts"] * 1e-6
        if e["ph"] == "B":
            stack.append((e["name"], e.get("cat"), t, e.get("args") or {}))
        elif e["ph"] == "E" and stack and stack[-1][0] == e["name"]:
            name, cat, t0, args = stack.pop()
            out.append((name, cat, t0, t, args))
        elif e["ph"] == "X":
            out.append((e["name"], e.get("cat"), t, t + e["dur"] * 1e-6, e.get("args") or {}))
    return out


def seconds_outside(ivs, holes):
    """Length of the union of `ivs` that no interval of `holes` covers:
    |A u B| - |B|."""
    ivs, holes = list(ivs), list(holes)
    return trace_reduce.union_seconds(ivs + holes) - trace_reduce.union_seconds(holes)


def parts():
    """-> {metric name: value} for the six metrics, or None."""
    ring = program_ring()
    if ring is None:
        return None
    events, evicted = ring
    if evicted or not any(e.get("cat") == "setup" for e in events):
        return None
    spans = intervals(events)
    trains = [s for name, cat, s, e, _ in spans if name == "train" and cat == "trainer"]
    cut = max(trains) if trains else float("inf")
    spans = [x for x in spans if x[2] < cut]
    own = [(s, e) for name, cat, s, e, _ in spans if name in OWN and cat in ("setup", "trainer")]
    # a jit interval's end is a reading of the tracer's own clock; its start
    # is that less a duration jax took on another clock: place it by its end
    jit = [x for x in spans if x[1] == "jit" and any(s <= x[3] <= e for s, e in own)]
    compiles = [x for x in jit if x[0] == "jit_compile"]
    compile_iv = [(s, e) for _, _, s, e, _ in compiles]
    trace_lower_iv = [(s, e) for name, _, s, e, _ in jit if name in ("jit_trace", "jit_lower")]
    build_iv = [(s, e) for name, cat, s, e, _ in spans if name in BUILD and cat == "setup"]
    return {
        "startup_import_s": trace_reduce.union_seconds(
            [(s, e) for name, cat, s, e, _ in spans if name == "import" and cat == "setup"]),
        "startup_build_s": seconds_outside(build_iv, compile_iv + trace_lower_iv),
        "startup_trace_lower_s": seconds_outside(trace_lower_iv, compile_iv),
        "startup_compile_s": trace_reduce.union_seconds(compile_iv),
        "startup_cache_misses": sum(1 for x in compiles if x[4].get("cache") == "miss"),
        "startup_programs": len(compiles),
    }


def read(name):
    found = parts()
    return None if found is None else found[name]
