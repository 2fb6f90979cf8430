"""tokens_per_s: see ``bench.readers.tokens_per_s``."""
from bench.readers import tokens_per_s as read  # noqa: F401
