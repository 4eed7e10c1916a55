"""95th percentile over all requests of the window of the time from the
request's arrival (the start of its wave) to its first token."""

from chipbench.stats import percentile


def read(rec):
    return percentile([(r["t_first"] - r["t_arrive"]) * 1e3
                       for r in rec.requests], 95)
