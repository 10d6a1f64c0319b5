"""95th percentile of reply time less due time over all requests due in the window."""
from benchmarks.readers import request_p95_s as read  # noqa: F401
