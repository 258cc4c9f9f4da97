"""Share of the traced window in which the chip was idle while the host
was inside a step: the idle gaps whose most-overlapping trainer phase
span is `ec.sample` or `ec.step` (bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split["steps"]
