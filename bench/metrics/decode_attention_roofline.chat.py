"""decode_attention_paged's share of its roofline at the real context
lengths (trace)."""
from harness.readers import decode_attention_roofline as read  # noqa: F401
