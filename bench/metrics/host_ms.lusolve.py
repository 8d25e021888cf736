"""Entry and dispatcher: mean host ms of a solve cell's entry call."""

from bench.readers import host_ms_per_call as read  # noqa: F401
