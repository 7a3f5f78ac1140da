"""The benchmark's own tests run on the CPU with four virtual devices (the
four-chip cell's rehearsal needs them).  Run them from the repo's root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 suite (``tests/``)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu(4)
