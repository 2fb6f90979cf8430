"""device_idle_share.code-long: see ``bench.readers.device_idle_share``."""
from bench.readers import device_idle_share as read  # noqa: F401
