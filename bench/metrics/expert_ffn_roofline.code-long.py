"""expert_ffn_roofline.code-long: see ``bench.moe_roofline``."""
from bench.moe_roofline import expert_ffn_roofline as read  # noqa: F401
