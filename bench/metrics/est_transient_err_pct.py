"""Error of the gate's transient bytes (replay and allocator: the
estimate less its persistent part), in percent of the measured transient
need (the ballast-bisected need less the measured persistent bytes),
with the difference floored at the bisection's resolution. Moves
``est_err_pct``.
"""


def read(ctx):
    est, meas = ctx.get("estimate"), ctx.get("measured")
    if not est or not meas:
        return None
    est_t = est["peak"] - est["persistent"]
    meas_t = meas["need"] - meas["persistent"]
    if meas_t <= 0:
        return None
    return 100.0 * max(abs(est_t - meas_t), meas["resolution"]) / meas_t
