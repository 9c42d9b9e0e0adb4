"""Process start to the first timed call: JAX and device init, traffic,
weights, compilation or cache load, warm-up."""


def read(ctx):
    return ctx["setup_s"]
