"""95th percentile over all requests of the window of the time per output
token after the first: (finish - first token) / (tokens - 1)."""

from chipbench.stats import percentile


def read(rec):
    xs = [(r["t_finish"] - r["t_first"]) * 1e3 / (len(r["generated"]) - 1)
          for r in rec.requests if len(r["generated"]) > 1]
    return percentile(xs, 95) if xs else None
