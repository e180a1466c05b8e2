"""repro.serve — continuous-batching inference engine on the task-graph
thread pool (DESIGN.md §7, §13).

``kv.py`` owns the per-family KV-cache layout knowledge (GQA append, MLA
compressed latents, SSM recurrent state, sliding-window rings) and the
block-pooled :class:`PagedKVCache` (fixed-size pages + per-sequence page
tables); ``engine.py`` schedules prefill/decode as prioritized tasks on the
work-stealing pool, batches sequences at iteration level, streams tokens
per tick, and under page pressure preempts the youngest resident back to
its deadline-ordered admit queue.
"""
from .engine import (
    DECODE_PRIORITY,
    PREFILL_PRIORITY,
    PREFILL_SOON,
    PREFILL_URGENT,
    DeadlineExceeded,
    GenRequest,
    QueueFull,
    RequestHandle,
    ServeEngine,
)
from .kv import PagedKVCache, pad_caches_to

__all__ = [
    "ServeEngine",
    "GenRequest",
    "RequestHandle",
    "QueueFull",
    "DeadlineExceeded",
    "PagedKVCache",
    "pad_caches_to",
    "PREFILL_PRIORITY",
    "PREFILL_SOON",
    "PREFILL_URGENT",
    "DECODE_PRIORITY",
]
