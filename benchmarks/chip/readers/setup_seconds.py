"""Process start to the first due request: start-up, weights, warm-up (and,
in a run that compiles, compilation), probes, preload."""


def read(ctx):
    return ctx["setup_s"]
