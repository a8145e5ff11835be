"""Share of the traced window in which no op ran on the device (%),
averaged over the cell's chips. Source: device trace."""

import harness


def read(ctx, spec):
    if ctx.trace is None:
        return None
    busy_s, window_s = harness.device_busy(ctx.trace)
    if window_s <= 0 or busy_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
