"""Share of the chip's busy time spent in the relabel program: device
time (the union of each run's op intervals) of the runs of the
trainer's `jit_ec_relabel`, over the union of device operations in the
traced window.  Unlike ec.relabel_share, the gathers that draw the
relabel subset are left out (bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    return spans.busy_share(run.trace, "jit_ec_relabel")
