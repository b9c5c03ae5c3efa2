"""Engine batching (``StreamEngine.stats()``): vector operations of
every kind served in the window over all rounds (retries included)
times the bucket's rows, in percent.  An update is two rounds."""


def read(ctx):
    r0, r1 = ctx["stats0"]["rounds_by_kind"], ctx["stats1"]["rounds_by_kind"]
    rounds = sum(r1[k] - r0[k] for k in r1)
    n = sum(r.n_ops * (2 if r.kind == "update" else 1) for r in ctx["reqs"])
    return 100.0 * n / (rounds * ctx["bucket"]) if rounds else None
