"""paged_decode_roofline.code-long: see ``bench.moe_roofline``."""
from bench.moe_roofline import paged_decode_roofline as read  # noqa: F401
