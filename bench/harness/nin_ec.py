"""Runner of the `nin_ec` kind, found by that name (bench/harness/ec.py
holds it under the name the benchmark's tests import)."""
from harness.ec import (CALIBRATE, METRIC, passed, readings,  # noqa: F401
                        round_work, run, trainer_for)
