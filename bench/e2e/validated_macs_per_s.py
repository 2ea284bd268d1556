"""Model MACs validated bit-exact per second: the MACs of every image
the window's requests validated, over the window."""


def read(run):
    return sum(r.work for r in run.requests) / run.span_s
