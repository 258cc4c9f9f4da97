"""Share of the chip's busy time spent in the relabel program of the EC
round on a language model: device time (the union of each run's op
intervals) of the runs of the trainer's `jit_ec_relabel`, over the
union of device operations in the traced window
(bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    return spans.busy_share(run.trace, "jit_ec_relabel")
