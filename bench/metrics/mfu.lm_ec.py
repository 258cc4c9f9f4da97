"""Model FLOPs utilization of the EC round on a language model, the
whole step's share of the chip's peak: forward and backward FLOPs of
every token the local and distillation steps consumed in the window
(3x the forward of projections, MLP, causal attention and LM head,
bench/harness/flops.py; remat's recompute and the relabel's forwards
are not counted), over the window, as a share of the bf16 peak."""


def reduce(run):
    return (100.0 * run.res["ec"]["train_flops"] / run.res["window_s"]
            / run.peak["bf16_flops"])
