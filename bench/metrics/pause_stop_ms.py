"""pause_stop_ms: see ``bench.readers.pause_stop_ms``."""
from bench.readers import pause_stop_ms as read  # noqa: F401
