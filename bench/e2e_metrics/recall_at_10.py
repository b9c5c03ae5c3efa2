"""Mean recall@10 of the fixed, seeded probe set issued after the
window, against the brute force over the final live set."""


def read(ctx):
    return ctx["recall"]
