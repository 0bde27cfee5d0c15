"""The arithmetic the per-layer readers share.  Each reader in
``metrics/`` takes what its metric needs from a run's context and
returns ``None`` where the run has nothing to read."""


def occupancy(ctx):
    lyr = ctx["layer"]
    if not lyr.get("slot_steps"):
        return None
    return 100.0 * lyr["busy_slot_steps"] / lyr["slot_steps"]


def step_ms(ctx):
    lyr = ctx["layer"]
    if not lyr.get("forward_passes"):
        return None
    return 1e3 * lyr["step_span_s"] / lyr["forward_passes"]


def step_mfu(ctx):
    lyr = ctx["layer"]
    if not lyr.get("least_s"):
        return None
    return 100.0 * lyr["least_s"] / lyr["window_s"]


def idle_share(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr["window_s"]:
        return None
    return 100.0 * tr["idle_share"]
