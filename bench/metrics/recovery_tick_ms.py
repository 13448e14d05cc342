"""Mean host time of the orchestrator ticks that fired a detection (AW
restore or EW remap)."""
from harness.readers import recovery_tick_ms as read  # noqa: F401
