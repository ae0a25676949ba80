"""Whole-round share of the chip's bf16 peak (%): the FLOPs a round needs
(bench/flops, by the server route the served rounds took) times the rounds
per second of the traced window.  The GLM rounds run in float64, which the
chip emulates, so this reads low against a bf16 peak by construction."""


def read(run):
    if run.flops_per_round is None or run.rounds <= 0 or run.window_s <= 0:
        return None
    return 100.0 * run.flops_per_round * run.rounds / run.window_s / run.peak["bf16_flops_per_s"]
