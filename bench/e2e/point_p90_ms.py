"""90th percentile of single-point latency over every request of the
window, in milliseconds (inclusive quantiles; one request reads its own
latency)."""

import statistics


def read(run):
    lat = [r.latency_s * 1e3 for r in run.requests]
    if len(lat) == 1:
        return lat[0]
    return statistics.quantiles(lat, n=10, method="inclusive")[8]
