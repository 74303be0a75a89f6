"""Own device time of the ops outside the tick's stage scopes (the scan
machinery, the chunk runner's row indexing) per host-tick traced."""

from bench.stage_reduce import us_per_host_tick


def read(ctx):
    return us_per_host_tick(ctx, "outside_tick")
