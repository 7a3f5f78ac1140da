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
