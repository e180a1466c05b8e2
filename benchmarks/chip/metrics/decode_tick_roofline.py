"""Roofline share of the decode tick (``jit__ptick``) in the traced window, in %.

The least time the chip could take for the window's ticks is the larger of
their needed operations over peak FLOP/s and their needed bytes over peak
bandwidth (``counts.py``): the unpadded weights read once per tick, plus the
keys and values of every live token at unpadded heads, for each token a tick
produced in the window. Divided by the ticks' device time. It counts the
work the algorithm needs, so padding, the page gather copy and the scatter
all show as a lower share, and no implementation can pass 100%.
"""

PROGRAM = "jit__ptick"


def decode_work(view):
    """(needed FLOPs, needed KV bytes) of the tokens ticks produced while traced."""
    ta, tb = view.traced
    flops = kv = 0.0
    for r in view.records:
        for j, t in enumerate(r.times):
            if j >= 1 and ta <= t <= tb:  # token 0 comes from the prefill
                ctx = r.prompt_len + j
                flops += view.dims.decode_flops(ctx)
                kv += ctx * view.dims.kv_bytes_per_token
    return flops, kv


def read(view):
    if view.trace is None:
        return None
    ticks = [(s, e) for n, s, e in view.trace["programs"] if n == PROGRAM]
    busy = sum(e - s for s, e in ticks)
    if not ticks or busy <= 0:
        return None
    flops, kv = decode_work(view)
    least = max(
        flops / view.peaks["bf16_flops_per_s"],
        (len(ticks) * view.dims.weight_bytes + kv) / view.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / busy
