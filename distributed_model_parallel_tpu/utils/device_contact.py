"""First device contact, shared by chip_smoke.py and the CLI drivers:
one attempt, and no run without the device it was meant for.

With no chip and ``JAX_PLATFORMS`` unset, JAX quietly hands out the CPU,
and a trainer or a benchmark would carry on there and print numbers that
look like a chip's. :func:`require_devices` therefore fails — the reason
on stderr, exit status :data:`EXIT_NO_ACCELERATOR` — when the backend
does not come up, or when what came up is not a TPU and the caller did
not ask for the CPU by setting ``JAX_PLATFORMS=cpu`` itself (the tests
and the CPU rehearsals do).
"""

from __future__ import annotations

import os
import sys

# Distinct, documented exit status for "no usable accelerator" so a
# supervisor can tell it from a crash in the program.
EXIT_NO_ACCELERATOR = 17


def require_devices(stage: str):
    """Contact the backend once. Returns the device list, or prints the
    reason and exits :data:`EXIT_NO_ACCELERATOR`."""
    import jax

    asked = os.environ.get("JAX_PLATFORMS", "<unset>")
    try:
        devs = jax.devices()
    except Exception as e:  # noqa: BLE001 - whatever it is, there is no device
        _refuse(stage, f"backend did not come up (JAX_PLATFORMS={asked!r}): "
                       f"{type(e).__name__}: {e}")
    platform = devs[0].platform
    if platform != "tpu" and asked != "cpu":
        _refuse(stage, f"JAX found no TPU (platform {platform!r}, "
                       f"JAX_PLATFORMS={asked!r}); set JAX_PLATFORMS=cpu "
                       f"to run on the CPU on purpose")
    return devs


def _refuse(stage: str, reason: str):
    print(f"[{stage}] no usable accelerator: {reason}", file=sys.stderr,
          flush=True)
    raise SystemExit(EXIT_NO_ACCELERATOR)
