"""Closed loop: ``concurrency`` callers, each waiting for its reply before
it sends the next request of the pool stream (``traffic.pool_stream``)."""
from harness import traffic

specs = traffic.pool_stream
