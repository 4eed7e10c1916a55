"""Mean rows (requests) advanced by one decode dispatch, over the window
(the program's ``dispatch_log``)."""


def read(rec):
    rows = [d["rows"] for d in rec.dispatches]
    return sum(rows) / len(rows) if rows else None
