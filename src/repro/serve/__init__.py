"""Serving substrate: batched engine with slot continuous batching, plus the
HTTP/SSE wire front-end (``repro.serve.server``) and the multi-replica
prefix-affinity router (``repro.serve.router``) — both imported lazily to
keep ``import repro.serve`` free of the client API stack."""
from repro.serve.engine import BatchedEngine, BlockAllocator, Request
from repro.serve.prefix import (PrefixIndex, SharedBlockPool,
                                chunked_reference_trajectory, prompt_digests,
                                ring_reference_futures)

__all__ = ["BatchedEngine", "BlockAllocator", "Request",
           "SharedBlockPool", "PrefixIndex", "prompt_digests",
           "ring_reference_futures", "chunked_reference_trajectory",
           "InferenceServer", "RouterServer", "ReplicaSupervisor",
           "PrefixAffinityScheduler"]

_LAZY = {
    "InferenceServer": "repro.serve.server",
    "RouterServer": "repro.serve.router",
    "ReplicaSupervisor": "repro.serve.router",
    "PrefixAffinityScheduler": "repro.serve.router",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is not None:
        import importlib
        return getattr(importlib.import_module(mod), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
