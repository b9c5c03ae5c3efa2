"""Index steps (profiler trace): device time of one ``query_step``
program run, averaged over the runs in the traced window."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    secs, runs = t.module("jit_query_step")
    return secs / runs * 1e3 if runs else None
