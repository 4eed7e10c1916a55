"""Share of the context positions the decode programs read that attention
needed, over the window's decode dispatches (the program's
``dispatch_log``): a row with ``fill`` cached positions that made ``made``
tokens needed fill + j + 1 positions for its j-th token; the program read
``read_positions`` per dispatch."""


def read(rec):
    logged = [d for d in rec.dispatches if "read_positions" in d]
    read = sum(d["read_positions"] for d in logged)
    needed = sum(m * f + m * (m + 1) // 2
                 for d in logged for f, m in zip(d["fills"], d["made"]))
    return 100.0 * needed / read if read else None
