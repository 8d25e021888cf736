"""Entry and dispatcher: seconds splitting, planning and verifying drains in
set-up, from the program's spans."""

from bench.program_trace import setup_plan_s as read  # noqa: F401
