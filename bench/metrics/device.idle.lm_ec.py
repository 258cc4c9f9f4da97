"""Share of the traced window in which no operation ran on the chip
(cells that report ec_tok_s)."""


def reduce(run):
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
