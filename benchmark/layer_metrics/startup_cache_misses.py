"""Programs compiled before the window because the persistent cache had no entry
for them: `jit_compile` events with `cache` = `miss` inside the program's own
spans.  0 in a warm run; it tells a cold set-up from a slower one
(startup_ring.py)."""

import startup_ring


def read(ctx):
    return startup_ring.read("startup_cache_misses")
