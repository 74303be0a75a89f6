"""The least time the chip could take for one host-tick (the bytes any
implementation must move, ``bench/bytes.py``, over the peak HBM bandwidth,
``bench/peaks.json``) as a share of the measured device time per
host-tick. Bandwidth is the bound: the tick does almost no arithmetic."""

from bench.metrics import device_us_per_host_tick


def read(ctx):
    us = device_us_per_host_tick.read(ctx)
    if not us or not ctx.get("peak"):
        return None
    least_us = ctx["tick_bytes"] / ctx["peak"]["hbm_bytes_per_s"] * 1e6
    return 100.0 * least_us / us
