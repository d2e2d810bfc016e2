"""On-demand build of the package's C++ helpers (paddle_tpu/native/*.cc).

The library's file name carries a hash of its source, so a binary built
from other source — left in the git-ignored build directory by another
checkout of the tree, say — is never the one that loads."""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
from typing import Optional, Sequence

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
)
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")


def build_native(source: str, stem: str, flags: Sequence[str] = ()) -> Optional[str]:
    """Path of ``lib<stem>-<source hash>.so`` built from
    ``paddle_tpu/native/<source>``, compiling it with g++ first if it is
    not there; None when the source or the compiler is missing or the
    build fails (callers fall back to pure Python)."""
    src = os.path.join(_NATIVE_DIR, source)
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:12]
    except OSError:
        return None
    so = os.path.join(_BUILD_DIR, f"lib{stem}-{digest}.so")
    if os.path.exists(so):
        return so
    try:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # per-pid temp + rename: concurrent processes must never CDLL a
        # half-written .so
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", *flags, src,
             "-o", tmp],
            check=True, capture_output=True,
        )
        os.replace(tmp, so)
    except (OSError, subprocess.CalledProcessError):
        return None
    for stale in glob.glob(os.path.join(_BUILD_DIR, f"lib{stem}*.so")):
        if stale != so:  # built from source that is gone
            try:
                os.remove(stale)
            except OSError:
                pass
    return so
