"""Runner of the `dense_lm_serve` kind, found by that name
(bench/harness/serve.py holds it)."""
from harness.serve import CALIBRATE, passed, run  # noqa: F401
