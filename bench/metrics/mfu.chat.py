"""Model FLOPs utilization of the engine's step programs: the FLOPs the
served tokens need (prefill of every first token and decode of every
later one in the window, counted by bench/harness/flops.py), over the
window, as a share of the chip's bf16 peak."""


def reduce(run):
    s = run.res["serve"]
    work = s["prefill_flops"] + s["decode_flops"]
    return 100.0 * work / run.res["window_s"] / run.peak["bf16_flops"]
