"""Model FLOP/s utilisation of training in the traced window, in %: the
operations the forward and backward passes need (``counts.py``: 6 per
matmul parameter, the tied head included, plus causal attention;
recomputation not counted) for the whole train steps chip 0 ran in the
trace, over the time from the first one's start to the last one's end
times the chips' summed bf16 peak."""


def read(view):
    if view.trace is None or view.trace["window_s"] <= 0:
        return None
    flops = view.trace["steps"] * view.tokens_per_step * view.dims.train_flops_per_token(
        view.seq_len
    )
    return 100.0 * flops / (view.trace["window_s"] * view.chips * view.peaks["bf16_flops_per_s"])
