"""The whole window's share of the chip's peak, for an MLA share: the
operations that every prompt and served token of the window needed over
interval times peak.  Outside the routed experts they come from the
shapes (``work_mla.request_flops``: projections, dense layers, router,
shared experts, attention in its multi-head form, the head once per
logits row); of the routed experts only the slots a held expert took
count, by the program's ``expert_slots`` summed over every prefill chunk
and decode dispatch of the window.  Nothing where the engine keeps no
MLA shapes or the program no such counter."""

from chipbench import work_mla, work_moe


def read(rec):
    s = getattr(rec, "mla", None)
    logs = [e for e in getattr(rec, "prefills", []) + rec.dispatches
            if "expert_slots" in e]
    if s is None or not logs:
        return None
    flops = sum(work_mla.request_flops(s, int(r["prompt"].size), len(r["generated"]))
                for r in rec.requests)
    flops += work_moe.expert_flops(rec.moe, work_moe.counted(logs)["expert_slots"])
    return 100.0 * flops / (rec.interval_s * rec.peak["bf16_flops"])
