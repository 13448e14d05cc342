"""Mean wall time of the KV checkpoint capture of a decode step, from the
program's ``step.checkpoint`` spans."""
from harness.program_spans import checkpoint_ms as read  # noqa: F401
