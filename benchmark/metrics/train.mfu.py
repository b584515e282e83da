"""train.mfu: the whole step's share of the chips' bf16 peak, in %.

Model FLOPs per token (``benchmark/flops.py``) times the traced window's
tokens per second, over the number of chips times the peak FLOP/s of the
device kind (``benchmark/peaks.json``).  Recomputation does not count.
"""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_flops", pathlib.Path(__file__).resolve().parents[1] / "flops.py")
flops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(flops)


def read(view):
    if view.trace is None:
        return None
    seq = view.cell["traffic"]["seq_len"]
    per_token = flops.train_flops_per_token(view.cell["config"], seq)
    rate = view.raw["tokens"] / (view.trace["window_ns"] / 1e9)
    chips = len(view.raw["devices"])
    return 100.0 * per_token * rate / (chips * view.peak["peak_flops_bf16"])
