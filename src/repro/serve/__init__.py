from .engine import ServeEngine, build_serve_fns, eos_done_mask  # noqa: F401
from .kv_cache import (BlockAllocator, OutOfBlocks,  # noqa: F401
                       PagedKVCache, blocks_per_request, scratch_table)
from .replica import ReplicaSet  # noqa: F401
from .scheduler import Request, Scheduler, SchedulerStats  # noqa: F401
