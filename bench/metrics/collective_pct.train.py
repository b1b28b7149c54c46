"""Exposed collective time of the traced training window, in percent: on
each device, the union of its collective operations' intervals
(``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all``,
``collective-permute``, with their ``-start`` / ``-done``) less the
intervals of its other operations but the containers (``while``,
``conditional``, ``call``), over the window; the largest over the
devices. Read from ``train_mesh_cell``'s reduction of the profiler's
trace. Moves ``tokens_per_s``.
"""


def read(ctx):
    coll = ctx.get("collectives")
    if not coll or not coll["devices"] or coll["window_s"] <= 0:
        return None
    worst = max(d["exposed_s"] for d in coll["devices"].values())
    return 100.0 * worst / coll["window_s"]
