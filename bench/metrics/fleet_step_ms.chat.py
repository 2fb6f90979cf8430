"""fleet_step_ms.chat: see ``bench.readers.fleet_step_ms``."""
from bench.readers import fleet_step_ms as read  # noqa: F401
