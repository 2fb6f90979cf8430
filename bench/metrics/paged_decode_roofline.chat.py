"""paged_decode_roofline.chat: see ``bench.readers.paged_decode_roofline``."""
from bench.readers import paged_decode_roofline as read  # noqa: F401
