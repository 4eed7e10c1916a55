"""Share of the roofline reached by the latent-attention kernel in the
decode programs of an MLA share: the least time the chip needs for the
attention that the traced dispatches asked for (``work_mla``: the
multi-head form's 2 H (nope + rope + v) operations per query and key, the
1,152 B latent of each needed position read once per row and step, and
each query's and output's width, in every layer) over the device time of
the kernel's operations (``mla_decode_attention*``) in the traced window,
in percent.  Nothing where the program has no such kernel, or the engine
no MLA shapes."""

from chipbench import harness, kernel_time, spans, work, work_mla

KERNEL = "mla_decode_attention"


def read(rec):
    s = getattr(rec, "mla", None)
    calls = [c for c in rec.traced_calls if c["program"] == "serve_step"]
    if s is None or rec.trace is None or not calls:
        return None
    t = kernel_time.kernel_seconds(spans.extract(harness.TRACE_DIR), KERNEL)
    if not t:
        return None
    n = work_mla.decode_attention(s, [r for c in calls for r in c["rows"]])
    return 100.0 * work.least_time(n["flops"], n["bytes"], rec.peak) / t
