"""Pre-jax-init CPU virtual-device shim shared by the training CLIs.

When ``JAX_PLATFORMS=cpu``, create a virtual CPU device per requested
parallel rank (the test/dev story for multi-chip code, SURVEY.md §4). The
device count must be set before the backend initializes — hence argv
pre-parsing instead of argparse.
"""

from __future__ import annotations

import os
import sys


def _argv_value(flag: str) -> str | None:
    argv = sys.argv
    for i, a in enumerate(argv):
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
        if a == flag and i + 1 < len(argv):
            return argv[i + 1]
    return None


def force_cpu_devices(
        flags: tuple[str | tuple[str, ...], ...] = ("--num-devices",)) -> None:
    """Create prod(<flag values>) virtual CPU devices (no-op off-CPU or
    when the product is 1). Call at module import, before any jax use.

    Each element of ``flags`` is one factor: either a flag name or a tuple
    of argparse aliases for the *same* option (first one present in argv
    wins — aliases never multiply with each other).
    """
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    n = 1
    for flag in flags:
        aliases = (flag,) if isinstance(flag, str) else flag
        for a in aliases:
            v = _argv_value(a)
            if v and v.isdigit():
                n *= int(v)
                break
    if n > 1:
        import jax
        jax.config.update("jax_num_cpu_devices", n)
