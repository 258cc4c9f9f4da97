"""Share of the traced window in which the chip was idle while the host
was at the end of a round of the EC round on a language model: the idle
gaps whose most-overlapping trainer phase span is `ec.loss_readback`,
`ec.relabel`, `ec.ma` or `ec.checkpoint` (bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split["round_end"]
