"""Host milliseconds of the serving loop's own work per turn: the mean,
over the program's ``serve.turn`` spans in the traced window, of a turn's
length less the time it spent blocked on a result (its ``serve.wait``
spans)."""

from chipbench.spans import traced_spans


def read(rec):
    turns, waited, cur = [], 0, None
    for s in traced_spans(rec) or ():
        if s["name"] == "serve.turn":
            if cur is not None:
                turns.append(cur - waited)
            cur, waited = s["end"] - s["start"], 0
        elif s["name"] == "serve.wait" and cur is not None:
            waited += s["end"] - s["start"]
    if cur is not None:
        turns.append(cur - waited)
    return sum(turns) / len(turns) / 1e6 if turns else None
