"""Mean wall time of one request's KV restore, from the program's
``recovery.restore`` spans."""
from harness.program_spans import restore_request_ms as read  # noqa: F401
