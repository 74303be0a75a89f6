"""Own device time under the tick's ``alloc`` scope per host-tick traced."""

from bench.stage_reduce import us_per_host_tick


def read(ctx):
    return us_per_host_tick(ctx, "alloc")
