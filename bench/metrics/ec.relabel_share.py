"""Share of the chip's busy time spent in the ensemble relabel: device
time of the runs of the relabel's programs (the one that scores every
member's relabel subset with all K members, and the gathers that draw
that subset), told apart in the trace as the programs shaped by the
subset's rows that run once a round (bench/harness/kernels.py), over
the union of device operations in the traced window."""
from harness import kernels


def reduce(run):
    return kernels.relabel_share(run)
