"""Host time of the window's ServingFrontend.step spans over its forward
passes (a burst of k model steps is k passes, any other dispatch one),
in ms."""

from bench.readers import step_ms as read  # noqa: F401
