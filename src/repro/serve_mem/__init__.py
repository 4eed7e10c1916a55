"""Paged-KV serving memory: the block pool / block table subsystem.

Cache memory — not slot count — is the scheduled resource of the paged
serving engine: fixed-size KV blocks live in one device-resident pool,
every in-flight request holds a *block table* mapping its logical token
positions onto pool blocks, and the host-side manager here does the
allocate / grow / release / watermark accounting that admission,
chunked prefill, and preemption decisions are made against.

The device twins read and write the pool through the same tables:
`repro.models.common.scatter_kv_paged` appends; paged decode reads each
row's live blocks with the Pallas kernel of
`repro.kernels.paged_attention` on a TPU, and
`repro.models.common.gather_kv_paged` gathers whole views elsewhere and
for chunked prefill.
"""

from repro.serve_mem.blocks import BlockPool, BlockTables
from repro.serve_mem.trace import make_mixed_trace

__all__ = ["BlockPool", "BlockTables", "make_mixed_trace"]
