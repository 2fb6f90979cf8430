"""reconf_stall_ms: see ``bench.readers.reconf_stall_ms``."""
from bench.readers import reconf_stall_ms as read  # noqa: F401
