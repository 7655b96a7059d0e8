"""Distributed-execution layer: sharding policy engine, mesh factory,
heterogeneous MPMD pipeline (paper §4.4)."""
from repro.dist import mesh, sharding  # noqa: F401
