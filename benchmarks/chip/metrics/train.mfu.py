"""Model FLOP/s utilisation over the traced window, in % of the chips'
bf16 peak: the FLOPs a training step requires per token (forward and
backward, recomputation and the embedding lookup not counted), times
the tokens of the window's whole steps, over the window's length."""


def read(run):
    c = run.counters
    if "flops_per_token" not in c:
        return None
    return (100 * c["flops_per_token"] * c["tokens"] / run.window_s
            / (len(run.devices) * run.peaks["bf16_flops_per_s"]))
