"""The client oracle's share of the chip's bf16 peak (%): the FLOPs its
forward and backward passes need in a round (bench/flops/bldnn.py
``oracle_per_round``, from the expert-layer counter the serve records
keep) over its device time (`oracle_ms`, `program_spans.layer_ms`) — the
hybrid block's share of its roofline, which its matrix multiplications
bound."""
import importlib.util
import os

from program_spans import layer_ms


def read(run):
    ms = layer_ms(run.trace, run.window, "oracle")
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "flops", "bldnn.py")
    spec = importlib.util.spec_from_file_location("bench_flops_bldnn_oracle", path)
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    total = flops.oracle_per_round(run.records)
    if ms is None or total is None or ms <= 0:
        return None
    return 100.0 * total / (ms / 1e3) / run.peak["bf16_flops_per_s"]
