"""Share of its roofline the Pallas paged-attention kernel reaches: the
least time the decode tokens of the window need in it, the larger of
FLOPs over the bf16 peak and live-KV bytes over HBM bandwidth (the
bound is bandwidth: one FLOP per byte at kv heads == heads), over the
kernel's device time in the trace.  Bytes count the live pages of the
decoding slots only: pages read for idle or mid-prompt slots are waste."""
from harness import kernels


def reduce(run):
    return kernels.paged_attn_roofline(run)
