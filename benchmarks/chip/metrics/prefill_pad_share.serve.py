"""Share of the positions the prefill programs computed that were padding:
100 x sum(bucket - length) / sum(bucket) over the traced window's prefill
chunks, each chunk's ``start``, ``length`` and ``bucket`` as the program's
``serve.prefill`` span carries them (its ``prefill_log`` entry).  Every
wave sends the same prompts, so the first wave's share is the window's."""

from chipbench.spans import traced_spans


def read(rec):
    chunks = [s["meta"] for s in traced_spans(rec) or () if s["name"] == "serve.prefill"]
    total = sum(c["bucket"] for c in chunks)
    return 100.0 * sum(c["bucket"] - c["length"] for c in chunks) / total if total else None
