"""moe_gemm's share of its roofline over the routed work (trace)."""
from harness.readers import moe_gemm_roofline as read  # noqa: F401
