"""Model FLOP/s utilization of the training step, in percent.

Forward plus backward matrix-multiply FLOPs of one step, counted from
shapes (``bench/flops.py``: no recomputation, no embedding lookup),
times steps per second in the traced window, over chips times the
chip's published bf16 peak (``bench/peaks.py``). Moves ``tokens_per_s``.
"""


def read(ctx):
    if not ctx.get("tokens_per_s") or not ctx.get("step_flops"):
        return None
    steps_per_s = ctx["tokens_per_s"] / ctx["tokens_per_step"]
    return 100.0 * steps_per_s * ctx["step_flops"] / (
        ctx["chips"] * ctx["peak_flops_per_s"])
