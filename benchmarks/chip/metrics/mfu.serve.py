"""The whole window's share of the chip's peak: operations that every
prompt and served token of the window needed, over interval times peak."""

from chipbench import serving


def read(rec):
    n = serving.needed(rec, rec.requests)
    flops = n["prefill_flops"] + n["decode_flops"]
    return 100.0 * flops / (rec.interval_s * rec.peak["bf16_flops"])
