"""pause_restore_ms: see ``bench.readers.pause_restore_ms``."""
from bench.readers import pause_restore_ms as read  # noqa: F401
