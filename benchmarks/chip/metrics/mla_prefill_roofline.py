"""Share of the roofline reached by the latent-attention kernel in the
prefill programs of an MLA share: the least time the chip needs for the
attention that the traced chunks asked for (``work_mla``: the multi-head
form's 2 H (nope + rope + v) operations per causal query and key, the
1,152 B latent of each position below the chunk's end read once per
chunk, and each query's and output's width, in every layer) over the
device time of the kernel's operations (``mla_chunk_attention*``) in the
traced window, in percent.  Nothing where the program has no such
kernel, or the engine no MLA shapes."""

from chipbench import harness, kernel_time, spans, work, work_mla

KERNEL = "mla_chunk_attention"


def read(rec):
    s = getattr(rec, "mla", None)
    calls = [c for c in rec.traced_calls if c["program"] == "prefill_chunk"]
    if s is None or rec.trace is None or not calls:
        return None
    t = kernel_time.kernel_seconds(spans.extract(harness.TRACE_DIR), KERNEL)
    if not t:
        return None
    n = [work_mla.prefill_attention(s, c["start"], c["length"]) for c in calls]
    return 100.0 * work.least_time(sum(w["flops"] for w in n),
                                   sum(w["bytes"] for w in n), rec.peak) / t
