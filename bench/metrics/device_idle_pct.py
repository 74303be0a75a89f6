"""Share of the traced window in which the worst device ran no op."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    w = tr["window_s"]
    return max(100.0 * (1.0 - b / w) for b in tr["busy_s"].values())
