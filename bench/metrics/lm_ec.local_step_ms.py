"""Device time of one local step of the EC round on a language model:
the mean over the window's runs of the trainer's `jit_ec_local_step`
of the union of that run's op intervals (bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    return spans.mean_run_ms(run.trace, "jit_ec_local_step")
