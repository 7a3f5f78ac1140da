"""Op-classification lists for autocast (O1/O4).

TPU re-design of ``apex/amp/lists/torch_overrides.py:7-136`` and
``functional_overrides.py:18-91``: names here are attributes of ``jax.numpy``,
``jax.lax`` or ``jax.nn`` instead of torch namespaces.

Categories (the reference's own):
  - LOW_PREC_FUNCS: MXU-friendly ops run in fp16/bf16 (FP16_FUNCS/BFLOAT16_FUNCS)
  - FP32_FUNCS:     numerically sensitive ops forced to fp32
  - CASTS:          binary ops promoted to the widest input type
  - SEQUENCE_CASTS: list-taking ops promoted across the sequence
Note jnp's native numpy-style promotion already widens mixed-dtype binary ops;
the CASTS wrappers exist to also *narrow consistently* when both inputs are
low-precision, and to mirror the reference's semantics exactly.
"""

# ops whose FLOPs land on the MXU — cast inputs to the low-precision type
# (reference FP16_FUNCS: conv*, matmul family, linear; torch_overrides.py:7-28)
JNP_LOW_PREC = [
    "dot",
    "matmul",
    "vdot",
    "inner",
    "outer",
    "tensordot",
    "einsum",
]
LAX_LOW_PREC = [
    "dot",
    "dot_general",
    "conv",
    "conv_general_dilated",
    "conv_transpose",
]
NN_LOW_PREC = []

# BFLOAT16 list == FP16 list minus prelu in the reference
# (torch_overrides.py:29-48); prelu has no jnp analog so the lists coincide.
JNP_LOW_PREC_BF16 = list(JNP_LOW_PREC)
LAX_LOW_PREC_BF16 = list(LAX_LOW_PREC)

# numerically sensitive ops — force fp32 (reference FP32_FUNCS:
# exp/log/pow/softmax/norm/sums/losses; torch_overrides.py:50-88)
JNP_FP32 = [
    "exp", "expm1", "log", "log10", "log1p", "log2",
    "power", "float_power",
    "cosh", "sinh", "tan",
    "arccos", "arcsin", "arctan",
    "cumprod", "cumsum",
    "prod", "sum", "mean", "var", "std",
]
LAX_FP32 = [
    "exp", "log", "log1p", "pow", "rsqrt", "logistic", "erf", "erfc", "erf_inv",
]
NN_FP32 = [
    "softmax", "log_softmax", "softplus", "logsumexp",
]
LINALG_FP32 = ["norm"]

# widest-type promotion for mixed binary ops (reference CASTS,
# torch_overrides.py:90-122)
JNP_CASTS = [
    "add", "subtract", "multiply", "divide", "true_divide",
    "equal", "greater", "greater_equal", "less", "less_equal", "not_equal",
]

# list-taking ops promoted across the whole sequence (reference SEQUENCE_CASTS:
# cat/stack; torch_overrides.py:124-131)
JNP_SEQUENCE_CASTS = [
    "concatenate",
    "stack",
    "hstack",
    "vstack",
]

# reference BANNED_FUNCS: binary_cross_entropy must not run in fp16
# (functional_overrides.py:84-91).  The jax analog is computing BCE from
# sigmoid outputs in low precision; we ban nothing by default but keep the
# mechanism for user registration.
BANNED_FUNCS = []
