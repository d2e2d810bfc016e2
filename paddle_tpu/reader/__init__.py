from paddle_tpu.reader import bucketing  # noqa: F401
from paddle_tpu.reader.bucketing import (  # noqa: F401
    sort_within_window,
    token_budget_batch,
)
from paddle_tpu.reader.decorator import (  # noqa: F401
    buffered,
    cache,
    chain,
    compose,
    firstn,
    map_readers,
    next_token_rows,
    shuffle,
    xmap_readers,
)
from paddle_tpu.reader.feeder import DataFeeder  # noqa: F401
from paddle_tpu.reader.loadgen import OpenLoopLoadGen, PrefixMixer  # noqa: F401
from paddle_tpu.reader.pass_cache import PassCache  # noqa: F401
