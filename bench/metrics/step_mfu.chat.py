"""Model FLOPs of the window's tokens over the window at the bf16 peak."""
from harness.readers import step_mfu as read  # noqa: F401
