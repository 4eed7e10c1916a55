"""Process start to the first measured request: imports, weights, the
engine's pool, loading or compiling every program, and the warm-up."""


def read(rec):
    return rec.setup_s
