"""Continuous-batching decode service: paged KV cache + inflight scheduler.

The training side of this repo already had the decode kernels
(``ops/pallas_attention.py``, ``models/transformer.generate``); this
package turns them into a serving engine:

* :mod:`serve.paged_kv` — the device-resident page pool and host-side
  page tables (vLLM-style paged KV cache);
* :mod:`serve.model` — the paged prefill/decode forward over
  ``models/transformer`` params (one jitted program each, any prompt
  length — the compile-cache story);
* :mod:`serve.scheduler` — request queue + iteration-level
  (continuous/Orca-style) batching: admission by free pages (billed
  post-sharing), mid-batch join/evict, chunked prefill interleaved with
  decode;
* :mod:`serve.prefix_cache` — the radix tree over token prefixes:
  refcounted copy-on-write page sharing, so a request whose prompt is
  cached admits with near-zero prefill (vLLM/SGLang-style);
* :mod:`serve.spec` — the n-gram self-drafting proposer behind
  speculative decoding (``ServeConfig.spec_k``): k drafted tokens per
  iteration, verified in one batched forward, committed only when the
  model's own choice agrees — spec-on/off token streams are identical;
* :mod:`serve.engine` — the loop wiring them together, with per-request
  SLO accounting (TTFT, per-token latency, queue wait, cache hit rate,
  draft accept rate) in the telemetry registry and typed ``serve``
  records;
* :mod:`serve.router` — SLO-aware replica selection:
  power-of-two-choices over live queue depth + page occupancy with a
  prefix-affinity bonus (deterministic, seeded);
* :mod:`serve.fleet` — the self-healing multi-replica tier: N engine
  replicas on disjoint device-pool slices behind the router, wired into
  the device-health sentinel — a degrading replica is quarantined and
  its in-flight requests migrate live to peers (KV pages exported by
  value, re-imported at the exact committed position), then the replica
  grows back after probation;
* :mod:`serve.cells` — cell topology: replicas grouped into named
  cells that fail (``kill_cell`` / ``slow_cell`` / ``partition``,
  utils/faults.py) and grow back as correlated units, with
  deterministic home-cell routing + cross-cell failover;
* :mod:`serve.traffic` — seeded production-traffic programs (diurnal,
  flash crowd, adversarial flood, mixed tenants) and the virtual
  :class:`~serve.traffic.SimClock` the chaos scenarios replay on.

See docs/SERVING.md for the anatomy, the fleet
kill-drill recipe and the scenario catalog.
"""

from distributed_model_parallel_tpu.serve.cells import (  # noqa: F401
    CellDirectory,
)
from distributed_model_parallel_tpu.serve.engine import (  # noqa: F401
    Engine,
    EngineKilled,
    ServeConfig,
)
from distributed_model_parallel_tpu.serve.fleet import (  # noqa: F401
    Replica,
    ServeFleet,
)
from distributed_model_parallel_tpu.serve.overload import (  # noqa: F401
    BrownoutController,
    CircuitBreaker,
)
from distributed_model_parallel_tpu.serve.router import (  # noqa: F401
    Router,
)
from distributed_model_parallel_tpu.serve.paged_kv import (  # noqa: F401
    PagedKVCache,
    PagePool,
    PagePoolError,
)
from distributed_model_parallel_tpu.serve.prefix_cache import (  # noqa: F401
    PrefixCache,
)
from distributed_model_parallel_tpu.serve.spec import (  # noqa: F401
    NGramProposer,
)
from distributed_model_parallel_tpu.serve.scheduler import (  # noqa: F401
    Request,
    Scheduler,
)
from distributed_model_parallel_tpu.serve.traffic import (  # noqa: F401
    SimClock,
    adversarial_flood,
    diurnal,
    flash_crowd,
    merge_traces,
    mixed_tenants,
)
