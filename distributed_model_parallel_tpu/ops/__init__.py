"""Communication and kernel ops:

* ``collectives`` — psum/ppermute/all_gather/reduce_scatter wrappers,
  bucketed coalesced allreduce, unused-param reporting
* ``ring_reduce`` — explicit bandwidth-optimal ring allreduce/reduce-scatter
  (the DDP Reducer's wire algorithm) over neighbor ppermutes
* ``ring_attention`` — ring + Ulysses sequence-parallel attention
* ``pallas_attention`` — on-chip blockwise flash attention kernel
* ``paged_attention`` — the serving engine's paged-KV-cache read: shared
  attend math, XLA gather fallback, Pallas paged-decode and
  paged-prefill kernels with scalar-prefetched page tables, their work
  following each row's live context (serve/, docs/SERVING.md)
* ``sparse`` — COO embedding gradients + DDP-style sparse allreduce
* ``moe`` — top-1 routed mixture-of-experts with expert-parallel all_to_all
"""

from distributed_model_parallel_tpu.ops.collectives import (  # noqa: F401
    all_gather_concat,
    bucketed_psum,
    ppermute_shift,
    psum_mean,
    reduce_scatter_mean,
    unused_param_mask,
)
from distributed_model_parallel_tpu.ops.ring_reduce import (  # noqa: F401
    ring_all_reduce,
    ring_psum_tree,
    ring_reduce_scatter,
)
from distributed_model_parallel_tpu.ops.ring_attention import (  # noqa: F401
    full_attention,
    ring_attention,
    ulysses_attention,
)
