"""Device busy time per replay scan step completed in the traced
window."""


def read(ctx):
    tr, steps = ctx["trace"], ctx["counters"]["steps"]
    if tr is None or tr["devices"] == 0 or steps == 0:
        return None
    return tr["busy_s"] / steps * 1e6
