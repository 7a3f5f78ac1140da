"""apex_tpu — a TPU-native acceleration library with the capabilities of
NVIDIA/ROCm Apex (reference: jithunnair-amd/apex), built on JAX/XLA/Pallas.

Four pillars, mirroring the reference (``apex/__init__.py:1-23``):
  1. ``apex_tpu.amp``        — mixed precision (opt levels O0-O5; bf16-native)
  2. ``apex_tpu.optimizers`` — fused optimizers (Pallas multi-tensor engine)
  3. ``apex_tpu.parallel``   — device-mesh distributed training
  4. ``apex_tpu.mlp`` / ``normalization`` / ``fp16_utils`` — fused layers and
     legacy manual mixed-precision utilities

Unlike the reference (its "no Python fallback" note,
``apex/__init__.py:10-16``), every Pallas kernel has a pure-XLA twin.  The
twin is CHOSEN — by an argument (``impl=``, ``use_pallas=``, ``attn_impl=``,
``backward=``) or by the platform (kernels interpret off-TPU) — never
substituted when a kernel fails: a kernel that does not compile is an error.
"""

import sys as _sys
import time as _time

_import_t0_ns = _time.perf_counter_ns()     # setup.import starts here ...
_jax_preloaded = "jax" in _sys.modules

from . import amp
from . import checkpoint
from . import fp16_utils
from . import multi_tensor_apply
from . import optimizers
from . import normalization
from . import parallel
from . import mlp
from . import models
from . import contrib
from . import pyprof
from . import telemetry
from . import resilience
from . import elastic
from . import interop
from . import RNN
from . import reparameterization

__version__ = "0.1.0"

# the set-up record (docs/telemetry.md): jax's compile events, by program
# name, from here on, and this import's own span
telemetry.events.install_compile_listener()
telemetry.trace.setup_tracer().add(     # ... and ends here
    "setup.import", (_time.perf_counter_ns() - _import_t0_ns) / 1e9,
    t0_ns=_import_t0_ns, jax_preloaded=_jax_preloaded)
