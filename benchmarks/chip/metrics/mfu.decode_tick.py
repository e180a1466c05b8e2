"""Model FLOP/s utilisation of decoding in the traced window, in %: the
needed operations of the tokens the ticks produced, over the wall time from
the first tick's start to the last tick's end times the chip's peak. The
host's gaps between ticks count against it, as they do against users."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "decode_tick_roofline", pathlib.Path(__file__).with_name("decode_tick_roofline.py")
)
_roof = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_roof)


def read(view):
    if view.trace is None:
        return None
    ticks = [(s, e) for n, s, e in view.trace["programs"] if n == _roof.PROGRAM]
    if len(ticks) < 2:
        return None
    span = ticks[-1][1] - ticks[0][0]
    flops, _kv = _roof.decode_work(view)
    return 100.0 * flops / (span * view.peaks["bf16_flops_per_s"])
