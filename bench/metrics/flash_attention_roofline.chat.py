"""flash_attention's share of its roofline over the real chunk tokens
(trace)."""
from harness.readers import flash_attention_roofline as read  # noqa: F401
