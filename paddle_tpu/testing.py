"""Test/dev-environment helpers."""

from __future__ import annotations

import os


def ensure_cpu_mesh(device_count: int = 8) -> None:
    """Default this process to a ``device_count``-device virtual CPU mesh.
    Environment defaults only — they must be in place before jax
    initializes its backends, and a platform or device count the caller
    already chose wins."""
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={device_count}"
    )
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def stage_reference_rnn_benchmark(
    dest: str, n: int = 64, seq_len: int = 100, vocab: int = 30000,
    seed: int = 0, min_seq_len: int = 0,
) -> None:
    """Stage the reference's rnn benchmark (benchmark/paddle/rnn) into
    ``dest`` with a synthesized ``imdb.train.pkl`` in the provider's exact
    pickle schema — ``(list_of_token_lists, labels)`` consumed by
    provider.py:process — plus a ``train.list`` of absolute paths.  Used
    by bench.py (full size) and the v1_compat test (tiny) so the schema
    lives in one place; zero-egress stand-in for the IMDB download that
    imdb.create_data would otherwise attempt.

    min_seq_len=0 keeps every review at exactly ``seq_len`` tokens (the
    fixed-shape bench); a positive value draws short-skewed review lengths
    in [min_seq_len, seq_len] (beta(2,3), IMDB-like) for the bucketing
    A/B."""
    import pickle
    import shutil

    import numpy as np

    src = "/root/reference/benchmark/paddle/rnn"
    for fn in ("rnn.py", "provider.py", "imdb.py"):
        shutil.copy(os.path.join(src, fn), dest)
    rng = np.random.RandomState(seed)
    if min_seq_len:
        lens = min_seq_len + np.floor(
            (seq_len - min_seq_len + 1) * rng.beta(2.0, 3.0, size=n)
        ).astype(int)
    else:
        lens = np.full(n, seq_len, int)
    x = [
        [int(t) for t in rng.randint(2, vocab, size=int(l))]
        for l in lens
    ]
    y = [int(v) for v in rng.randint(0, 2, size=n)]
    pkl = os.path.join(dest, "imdb.train.pkl")
    with open(pkl, "wb") as f:
        pickle.dump((x, y), f, protocol=2)
    with open(os.path.join(dest, "train.list"), "w") as f:
        f.write(pkl + "\n")
