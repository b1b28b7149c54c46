"""Share of the traced training window in which no operation ran on the
device, in percent: 1 - busy / window, busy being the union of the
``XLA Ops`` intervals in the profiler's trace (``bench/trace_reduce.py``),
averaged over the chips. Moves ``tokens_per_s``.
"""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
