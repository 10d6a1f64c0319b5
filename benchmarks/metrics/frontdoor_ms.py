"""Median client reply time less the time the replica held the request."""
from benchmarks.readers import frontdoor_ms as read  # noqa: F401
