"""Seconds from process start to the window's opening: data, index
init, bulk load, warm-up and, in a run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
