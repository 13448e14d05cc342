"""Mean host time of the engine step outside its device-bound calls and
their drains, from the program's ``step`` spans."""
from harness.program_spans import step_host_ms as read  # noqa: F401
