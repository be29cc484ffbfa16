"""Cohort futures: ``max_in_flight`` patients at a time, ``n_futures`` each,
taken in turn from the pool stream of histories (``traffic.pool_stream``)."""
from harness import traffic

specs = traffic.pool_stream
