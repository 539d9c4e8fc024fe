"""Model FLOP/s utilization of the train step: model FLOPs per token
(``bench/flops.py``, no recompute) times the tokens of every step of the
window, over the window's seconds, the chips and their bf16 peak."""


def read(m):
    done = m.flops_per_token * m.tokens_per_step * m.steps
    return 100.0 * done / (m.window_s * m.chips
                           * m.peaks["bf16_flops_per_s"])
