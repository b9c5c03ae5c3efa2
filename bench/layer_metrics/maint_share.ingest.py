"""Maintenance (profiler trace): device time of the ``seal_step`` and
``merge_step`` programs over the device's busy time, in percent."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.busy_s:
        return None
    secs = t.module("jit_seal_step")[0] + t.module("jit_merge_step")[0]
    return 100.0 * secs / t.busy_s
