"""Model FLOPs utilization of the EC round: NiN forward and backward
FLOPs of every image the local and distillation steps consumed in the
window (3x the forward, bench/harness/flops.py; relabel forwards are
not counted), over the window, as a share of the chip's bf16 peak."""


def reduce(run):
    return (100.0 * run.res["ec"]["train_flops"] / run.res["window_s"]
            / run.peak["bf16_flops"])
