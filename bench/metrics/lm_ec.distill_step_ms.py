"""Device time of one distill step of the EC round on a language model:
the mean over the window's runs of the trainer's `jit_ec_distill_step`
of the union of that run's op intervals (bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    return spans.mean_run_ms(run.trace, "jit_ec_distill_step")
