"""Host microseconds per admission spent planning the prompt's prefill
chunks (the user-defined schedule's clause, ``plan_prefill_chunks``): the
mean length of the program's ``serve.plan`` spans in the traced window."""

from chipbench.spans import traced_spans


def read(rec):
    plans = [s["end"] - s["start"] for s in traced_spans(rec) or () if s["name"] == "serve.plan"]
    return sum(plans) / len(plans) / 1e3 if plans else None
