"""Opt-level property system for TPU amp.

TPU-native re-design of the reference opt-level table (``apex/amp/frontend.py:7-254``):
``Properties`` is a validated dataclass-style options object; presets O0-O5 configure
it.  On TPU, bf16 modes (O4/O5) are the *native* fast path — bf16 shares fp32's
exponent range so ``loss_scale`` defaults to 1 there, exactly as the reference
states ("Loss scaling is not required in O4 mode", ``frontend.py:207-224``).

Instead of torch dtypes, properties carry ``jnp.dtype``s, and instead of
monkey-patching model.forward we return pure functions/policies that the
``apex_tpu.amp.initialize`` facade applies to param pytrees and step functions.
"""
from __future__ import annotations

import jax.numpy as jnp

_ALLOWED = {
    "enabled",
    "opt_level",
    "cast_model_type",
    "patch_functions",
    "patch_functions_type",
    "keep_batchnorm_fp32",
    "master_weights",
    "loss_scale",
    "flash_attn_backward",
}

# flash-attention gradient route (contrib.multihead_attn.flash): "auto"
# defers to the env pin, else the Pallas kernels; "pallas"/"xla" force the path
# process-wide via flash.set_default_backward (applied by initialize()).
# flash.BACKWARD_IMPLS is the single source of truth for the valid
# values; imported lazily so this module never pulls Pallas in at
# import time.


def _flash_backwards():
    from ..contrib.multihead_attn.flash import BACKWARD_IMPLS
    return BACKWARD_IMPLS


class Properties:
    """Mutable options bag with validation, mirroring ``frontend.py:7-113``.

    Unlike the reference we validate eagerly on every ``__setattr__`` and allow
    the same "options=" override flow after a preset is applied.
    """

    def __init__(self):
        self.options = {
            "enabled": False,
            "opt_level": None,
            "cast_model_type": None,
            "patch_functions": False,
            "patch_functions_type": None,
            "keep_batchnorm_fp32": None,
            "master_weights": None,
            "loss_scale": 1.0,
            "flash_attn_backward": "auto",
        }

    def _update_options_dict(self, new_options):
        for k, v in new_options.items():
            if k in self.options:
                self.options[k] = v
            else:
                raise ValueError(f"Tried to set unexpected option {k}")

    def __getattr__(self, name):
        if "options" in self.__dict__ and name in self.__dict__["options"]:
            return self.options[name]
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if "options" in self.__dict__:
            if name not in self.options:
                raise AttributeError(
                    f"Tried to set unexpected option {name}; valid: {sorted(_ALLOWED)}")
            if name == "cast_model_type":
                if self.opt_level == "O1" and value is not None:
                    if value is not False:
                        raise RuntimeError(
                            "O1 inserts casts around ops, so the model weights themselves "
                            "should remain fp32 (cast_model_type must be None/False with O1).")
                self.options[name] = _as_dtype(value)
            elif name == "patch_functions_type":
                self.options[name] = _as_dtype(value)
            elif name == "loss_scale":
                if value == "dynamic":
                    self.options[name] = value
                else:
                    self.options[name] = float(value)
            elif name == "flash_attn_backward":
                if value is None:
                    value = "auto"
                if value not in _flash_backwards():
                    raise ValueError(
                        f"flash_attn_backward must be one of "
                        f"{_flash_backwards()}, got {value!r}")
                self.options[name] = value
            else:
                self.options[name] = value
        else:
            super().__setattr__(name, value)

    def __repr__(self):
        return "Properties(" + ", ".join(f"{k}={v}" for k, v in self.options.items()) + ")"

    # hashable so Properties can ride as static jit metadata in AmpState
    def _key(self):
        return tuple(sorted((k, str(v)) for k, v in self.options.items()))

    def __eq__(self, other):
        return isinstance(other, Properties) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())


def _as_dtype(value):
    if value is None or value is False:
        return value
    return jnp.dtype(value)


class O0:
    brief = "O0:  Pure FP32 training."

    def __call__(self, properties):
        properties.enabled = True
        properties.opt_level = "O0"
        properties.cast_model_type = jnp.float32
        properties.patch_functions = False
        properties.patch_functions_type = None
        properties.keep_batchnorm_fp32 = None
        properties.master_weights = False
        properties.loss_scale = 1.0
        return properties


class O1:
    brief = "O1:  Insert automatic casts around jax.numpy functions (fp16)."

    def __call__(self, properties):
        properties.enabled = True
        properties.opt_level = "O1"
        properties.cast_model_type = None
        properties.patch_functions = True
        properties.patch_functions_type = jnp.float16
        properties.keep_batchnorm_fp32 = None
        properties.master_weights = None
        properties.loss_scale = "dynamic"
        return properties


class O2:
    brief = "O2:  FP16 training with FP32 batchnorm and FP32 master weights."

    def __call__(self, properties):
        properties.enabled = True
        properties.opt_level = "O2"
        properties.cast_model_type = jnp.float16
        properties.patch_functions = False
        properties.patch_functions_type = None
        properties.keep_batchnorm_fp32 = True
        properties.master_weights = True
        properties.loss_scale = "dynamic"
        return properties


class O3:
    brief = "O3:  Pure FP16 training."

    def __call__(self, properties):
        properties.enabled = True
        properties.opt_level = "O3"
        properties.cast_model_type = jnp.float16
        properties.patch_functions = False
        properties.patch_functions_type = None
        properties.keep_batchnorm_fp32 = False
        properties.master_weights = False
        properties.loss_scale = 1.0
        return properties


class O4:
    brief = "O4:  Insert automatic casts around jax.numpy functions (bf16; TPU-native)."

    def __call__(self, properties):
        properties.enabled = True
        properties.opt_level = "O4"
        properties.cast_model_type = None
        properties.patch_functions = True
        properties.patch_functions_type = jnp.bfloat16
        properties.keep_batchnorm_fp32 = None
        properties.master_weights = None
        # bf16 shares fp32's exponent range; no scaling needed (frontend.py:207-224).
        properties.loss_scale = 1.0
        return properties


class O5:
    brief = "O5:  BFLOAT16 training with FP32 batchnorm and FP32 master weights (TPU-native)."

    def __call__(self, properties):
        properties.enabled = True
        properties.opt_level = "O5"
        properties.cast_model_type = jnp.bfloat16
        properties.patch_functions = False
        properties.patch_functions_type = None
        properties.keep_batchnorm_fp32 = True
        properties.master_weights = True
        properties.loss_scale = 1.0
        return properties


# Mirrors ``opt_levels`` dict at frontend.py:249-254.
opt_levels = {
    "O0": O0(),
    "O1": O1(),
    "O2": O2(),
    "O3": O3(),
    "O4": O4(),
    "O5": O5(),
}
