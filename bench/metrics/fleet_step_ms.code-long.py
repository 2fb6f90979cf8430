"""fleet_step_ms.code-long: see ``bench.readers.fleet_step_ms``."""
from bench.readers import fleet_step_ms as read  # noqa: F401
