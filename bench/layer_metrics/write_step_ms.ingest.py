"""Index steps (profiler trace): device time of one ``insert_step`` or
``delete_step`` program run, averaged over those runs."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    si, ni = t.module("jit_insert_step")
    sd, nd = t.module("jit_delete_step")
    return (si + sd) / (ni + nd) * 1e3 if ni + nd else None
