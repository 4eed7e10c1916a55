"""Serving example: continuous batching where the prefill plan is a UDS.

``PagedServeLoop`` admits a request as soon as the KV block pool holds its
prompt, and the schedule clause splits each prompt's prefill into chunks
that interleave with decode dispatches: ``schedule(static)`` prefills in
bursts of ``prefill_chunk`` tokens, ``schedule(dynamic,1)`` in the smallest
chunks (least head-of-line blocking for in-flight decodes, most
dispatches), and guided/factoring policies start coarse and refine toward
the prompt's tail.

    PYTHONPATH=src python examples/serve_continuous_batching.py
"""

import time

import numpy as np

from repro.configs import get_smoke_config
from repro.launch.serve import PagedServeLoop, Request


def main() -> None:
    cfg = get_smoke_config("qwen2.5-3b")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(4, 32))
                                        ).astype(np.int32),
                    max_new=6)
            for i in range(12)]

    for sched in ("static", "guided", "fac2"):
        loop = PagedServeLoop(cfg, num_blocks=32, block_size=8,
                              max_context=64, concurrency=4,
                              scheduler=sched, decode_steps=2,
                              prefill_chunk=16)
        t0 = time.perf_counter()
        out = loop.run([Request(r.rid, r.prompt, r.max_new) for r in reqs])
        dt = time.perf_counter() - t0
        toks = sum(len(v) for v in out.values())
        print(f"schedule({sched:8s}): {len(out)} requests, {toks} tokens, "
              f"{dt:.2f}s ({toks/dt:.1f} tok/s), "
              f"{loop.last_stats['prefill_dispatches']} prefill chunks")


if __name__ == "__main__":
    main()
