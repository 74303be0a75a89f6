"""Device busy time in the traced window per host-tick run there, averaged
over the devices used."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not ctx.get("host_ticks_traced"):
        return None
    busy = sum(tr["busy_s"].values()) / len(tr["busy_s"])
    return busy / ctx["host_ticks_traced"] * 1e6
