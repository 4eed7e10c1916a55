"""Share of the roofline reached by the prefill programs: the least time
the chip needs for what the traced prefill calls were asked (needed
operations at peak, or needed bytes at peak bandwidth, whichever is
longer) over those calls' device time, in percent."""

from chipbench import serving, work


def read(rec):
    t = serving.program_seconds(rec, serving.PREFILL)
    if not t:
        return None
    n = serving.traced_work(rec, serving.PREFILL)
    return 100.0 * work.least_time(n["flops"], n["bytes"], rec.peak) / t
