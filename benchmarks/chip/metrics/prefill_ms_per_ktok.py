"""Device milliseconds of the prefill programs per 1,000 tokens they were
asked to prefill, over the traced calls (re-prefill after preemption
included: it is work the program did)."""

from chipbench import serving


def read(rec):
    t = serving.program_seconds(rec, serving.PREFILL)
    n = serving.traced_work(rec, serving.PREFILL)["tokens"]
    return t * 1e3 / (n / 1e3) if t and n else None
