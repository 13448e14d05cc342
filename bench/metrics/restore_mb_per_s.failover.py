"""Bytes restored per second of restore, from the program's
``recovery.restore`` spans."""
from harness.program_spans import restore_mb_per_s as read  # noqa: F401
