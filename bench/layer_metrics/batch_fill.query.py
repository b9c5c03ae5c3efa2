"""Engine batching (``StreamEngine.stats()``): query vectors served in
the window over query rounds times the bucket's rows, in percent."""


def read(ctx):
    rounds = (ctx["stats1"]["rounds_by_kind"]["query"]
              - ctx["stats0"]["rounds_by_kind"]["query"])
    n = sum(r.n_ops for r in ctx["reqs"] if r.kind == "query")
    return 100.0 * n / (rounds * ctx["bucket"]) if rounds else None
