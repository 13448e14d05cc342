"""Gateway queue wait: median of admission minus due time of the window's
requests, from ``Gateway.stats.queue_delay``."""
from harness.readers import queue_wait_p50_s as read  # noqa: F401
