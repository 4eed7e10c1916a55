"""Preemptions per request over the window: how often the KV block
manager evicted a request to let an older one grow."""


def read(rec):
    return sum(r["preemptions"] for r in rec.requests) / len(rec.requests)
