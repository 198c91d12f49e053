"""Runner for `kind: closed_loop`: the serving runner; the kind decides how the
load generator offers the plan (see `harness/serve.py`)."""
from chipbench.harness.serve import run  # noqa: F401
