"""Design points evaluated per second: every point of the window's
requests over the window, first start to last end."""


def read(run):
    return sum(r.work for r in run.requests) / run.span_s
