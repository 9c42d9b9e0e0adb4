"""Packets of every call in the measured window over its elapsed
seconds (host clock)."""


def read(ctx):
    w = ctx["window"]
    return w["packets"] / w["seconds"]
