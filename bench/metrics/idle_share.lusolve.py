"""Device: idle share of the traced window in a solve cell, %."""

from bench.readers import idle_share as read  # noqa: F401
