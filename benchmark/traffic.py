"""The one traffic generator.  A mix is a data file of parameters under
`traffic/`; its `kind` names the law the inputs are drawn by, a file
`traffic_kinds/<kind>.py` with `make_corpus(mix, cfg, seed)` (the batches as
a user's reader yields them), `as_arrays(batch)` (one batch as the plain
reference takes it), `items(batch)` (what the throughput counts) and
`lengths(batch)` (what the FLOP functions take)."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def kind(mix):
    from refsteps import load_by_name

    return load_by_name("traffic_kinds", mix["kind"])
