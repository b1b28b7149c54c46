"""Error of the gate's persistent bytes (orchestrator: parameters,
optimizer state and what else outlives a step), in percent of the bytes
the device holds with the parameters and optimizer state live, less the
runtime's own baseline. Moves ``est_err_pct``.
"""


def read(ctx):
    est, meas = ctx.get("estimate"), ctx.get("measured")
    if not est or not meas or meas["persistent"] <= 0:
        return None
    return 100.0 * abs(est["persistent"] - meas["persistent"]) \
        / meas["persistent"]
