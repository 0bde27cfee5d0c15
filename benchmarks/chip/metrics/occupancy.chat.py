"""Share of the slot rows the window's dispatches computed that a request
consumed: delta busy_slot_steps over delta slot_steps (EngineStats), in
%."""

from bench.readers import occupancy as read  # noqa: F401
