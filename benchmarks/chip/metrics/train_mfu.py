"""train_mfu: model FLOP/s utilisation of the federated local updates.

Forward and backward operations per training window (``flops.train_flops``:
three forwards, nothing recomputed counted) times the windows every round
trains (clients x local steps x batch) times the rounds completed in the
traced window, over the window's length and the chip's peak bf16 rate.
Evaluation forwards are not counted.
"""


def read(rc):
    c = rc.counts
    if not c.get("rounds"):
        return None
    flops = (rc.flops.train_flops(rc.config) * c["train_rows_per_round"]
             * c["rounds"])
    return 100.0 * flops / c["window_s"] / rc.peaks["bf16_flops_per_s"]
