"""The window's share of the chip's roofline: the least time each forward
pass or train step requires (work.py, from shapes) summed and divided by
the window, in %."""

from bench.readers import step_mfu as read  # noqa: F401
