"""Mean host time of the jitted decode call, to ``block_until_ready``."""
from harness.readers import decode_step_ms as read  # noqa: F401
