"""Tokens served per second: every token of every request of the window
over the whole interval, from the first wave's start to the last's end."""


def read(rec):
    return sum(len(r["generated"]) for r in rec.requests) / rec.interval_s
