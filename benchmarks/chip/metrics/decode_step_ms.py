"""Device milliseconds of the decode programs per decode step (traced
dispatches times the steps each runs)."""

from chipbench import serving


def read(rec):
    t = serving.program_seconds(rec, serving.DECODE)
    steps = (serving.traced_work(rec, serving.DECODE)["calls"]
             * int(rec.serve["decode_steps"]))
    return t * 1e3 / steps if t and steps else None
