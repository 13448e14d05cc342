"""Mean host time of the jitted prefill-chunk call, to
``block_until_ready``."""
from harness.readers import prefill_chunk_ms as read  # noqa: F401
