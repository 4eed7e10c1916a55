"""Share of the roofline reached by the decode programs: the least time
the chip needs for the tokens the traced dispatches made (weights once
per step, each token's context once, its append) over those dispatches'
device time, in percent."""

from chipbench import serving, work


def read(rec):
    t = serving.program_seconds(rec, serving.DECODE)
    if not t:
        return None
    n = serving.traced_work(rec, serving.DECODE)
    return 100.0 * work.least_time(n["flops"], n["bytes"], rec.peak) / t
