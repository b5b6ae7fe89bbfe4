"""Instance-batched solver: pad/bucket/vmap many TSP instances per device.

- batch.py     pads instances to power-of-two bucket sizes with masked
               phantom cities and stacks them into a ProblemBatch;
- engine.py    vmaps core.aco.colony_step over the instance axis so one
               jitted call advances B colonies, with per-instance budgets
               and a done-mask early exit;
- service.py   a drain-the-queue request loop with throughput stats
               and supervisor/checkpoint crash recovery;
- streaming.py continuous batching: per-bucket resident slot pools with
               chunked stepping, harvest + refill surgery mid-run,
               priority/deadline admission, deadline eviction and
               backpressure;
- placement.py multi-device fabric: shard_map the engine's instance axis
               over a 1-D device mesh (phantom-slot padding for uneven
               batches), place streaming pools per device;
- programs.py  ahead-of-time program cache: persistent XLA compile cache,
               bucket-ladder warmup (AOT lower+compile before traffic)
               and neighbour-bucket admission routing.

See DESIGN.md §8 for the bucketing policy and masking invariants, §9 for
the streaming slot lifecycle, §11 for the placement layer, §16 for the
program cache.
"""
from .batch import (ProblemBatch, bucket_ladder, bucket_size,  # noqa: F401
                    make_batch, padded_problem)
from .engine import (init_state, init_states, run_batch,  # noqa: F401
                     solve_instances)
from .programs import (ProgramCache, ProgramKey,  # noqa: F401
                       check_neighbour_route, compile_cache_dir,
                       enable_persistent_cache, persistent_cache_stats)
from .placement import data_mesh, run_batch_sharded  # noqa: F401
from .service import SolveResult, SolverService  # noqa: F401
from .streaming import (AdmissionError, StreamingPool,  # noqa: F401
                        StreamingSolverService, TraceItem,
                        make_poisson_trace, replay_trace)
