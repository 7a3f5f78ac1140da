"""amp frontend: ``initialize`` / ``scale_loss`` / state (de)serialization.

Re-design of ``apex/amp/frontend.py:258-467`` + ``_initialize.py:145-265`` for
a functional world.  The reference mutates models/optimizers in place; here
``initialize`` takes the model's param pytree (and optionally an apex_tpu
fused optimizer) and returns an ``AmpState`` bundle of pure pieces:

    amp_state = amp.initialize(params, optimizer, opt_level="O5", num_losses=1)
    amp_state.model_params      # params cast per opt level (bf16/fp16/fp32)
    amp_state.master_params     # fp32 masters (O2/O5) or None
    amp_state.scalers           # tuple[ScalerState], one per loss_id
    amp_state.cast_input(x)     # input-cast helper (patched-forward analog)

plus pure step helpers (``amp_step``) that implement the full
scale → grad → unscale → check → (skip-)update → rescale pipeline of
``handle.scale_loss`` (handle.py:16-158) + ``_process_optimizer`` as one
jittable function.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from . import amp as _amp
from . import scaler as _scaler
from .properties import Properties, opt_levels
from ..pyprof import annotate, annotate_function
from ..telemetry import events as _tel_events
from ..utils import pytree as _pt


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AmpState:
    """The bundle returned by initialize().  ``properties`` and ``optimizer``
    are static pytree metadata (trace constants); params/scalers/opt_state are
    traced leaves, so an AmpState threads directly through jit."""
    model_params: Any               # cast params
    master_params: Any              # fp32 masters or None
    scalers: Tuple[_scaler.ScalerState, ...]
    opt_state: Any                  # optimizer state or None
    properties: Any = dataclasses.field(metadata=dict(static=True), default=None)
    optimizer: Any = dataclasses.field(metadata=dict(static=True), default=None)
    cast_model_outputs: Any = dataclasses.field(metadata=dict(static=True),
                                                default=None)

    def _replace(self, **kw):
        return dataclasses.replace(self, **kw)

    # -- convenience ---------------------------------------------------------
    @property
    def loss_scale(self):
        return self.scalers[0].loss_scale

    def cast_input(self, x):
        return _cast_floats(x, self.properties.cast_model_type)

    def cast_output(self, y):
        """Apply the ``cast_model_outputs`` dtype (reference
        ``_initialize.py:185-190``: the forward patch's output_caster) — a
        no-op unless initialize() was given one."""
        return _cast_floats(y, self.cast_model_outputs)

    def params_for_eval(self):
        """fp32 view of params (the O2 state_dict hook, _initialize.py:133-142)."""
        if _flat_masters_active(self):
            return _master_flattener(self).unflatten(self.opt_state.master)
        src = self.master_params if self.master_params is not None else self.model_params
        return jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, src)


def _cast_floats(tree, dt):
    """Cast floating array leaves to ``dt`` (None/False = no-op); python
    scalars and integer arrays pass through (_pt.cast_inputs predicate)."""
    if dt in (None, False):
        return tree
    args, _ = _pt.cast_inputs((tree,), {}, dt)
    return args[0]


def initialize(params, optimizer=None, opt_level="O1", *,
               num_losses=1, verbosity=1,
               cast_model_type=None, patch_functions=None,
               keep_batchnorm_fp32=None, master_weights=None,
               loss_scale=None, min_loss_scale=1.0,
               max_loss_scale=2.0 ** 24,
               allow_incoming_model_not_fp32=False,
               cast_model_outputs=None,
               flash_attn_backward=None) -> "AmpState | list[AmpState]":
    """Opt-level driven setup (``frontend.py:258-425``).

    params: fp32 model param pytree.  optimizer: an apex_tpu fused optimizer
    (algorithm object) — its state is created against the *master* params.
    Overrides after the preset mirror the reference's kwarg override flow
    (frontend.py:401-419).

    Passing matching LISTS for both ``params`` and ``optimizer`` returns a
    list of independent AmpStates (the reference's lists-of-models API,
    frontend.py:296-331).
    """
    # list-of-models API shape (frontend.py:296-331: "If either the
    # ``models`` or ``optimizers`` args were lists, the corresponding
    # return value will also be a list"): one AmpState per model, paired
    # with its optimizer by position.  Triggered ONLY when BOTH args are
    # top-level lists/tuples — a list is a legal pytree for a single
    # model (pipeline stages, interop param lists), so params alone is
    # ambiguous; a matching list of optimizers is the unambiguous signal.
    if isinstance(params, (list, tuple)) \
            and isinstance(optimizer, (list, tuple)):
        opts = list(optimizer)
        if len(opts) != len(params):
            raise ValueError(
                f"{len(params)} models but {len(opts)} optimizers")
        kw = dict(num_losses=num_losses, verbosity=verbosity,
                  cast_model_type=cast_model_type,
                  patch_functions=patch_functions,
                  keep_batchnorm_fp32=keep_batchnorm_fp32,
                  master_weights=master_weights, loss_scale=loss_scale,
                  min_loss_scale=min_loss_scale,
                  max_loss_scale=max_loss_scale,
                  allow_incoming_model_not_fp32=allow_incoming_model_not_fp32,
                  cast_model_outputs=cast_model_outputs,
                  flash_attn_backward=flash_attn_backward)
        return [initialize(p, o, opt_level, **kw)
                for p, o in zip(params, opts)]

    if opt_level not in opt_levels:
        raise RuntimeError(f"Unexpected optimization level {opt_level}; "
                           "options are 'O0'..'O5'.")
    props = opt_levels[opt_level](Properties())
    for name, val in (("cast_model_type", cast_model_type),
                      ("patch_functions", patch_functions),
                      ("keep_batchnorm_fp32", keep_batchnorm_fp32),
                      ("master_weights", master_weights),
                      ("loss_scale", loss_scale),
                      ("flash_attn_backward", flash_attn_backward)):
        if val is not None:
            setattr(props, name, val)
    if verbosity:
        print(f"apex_tpu.amp: opt_level {opt_level} -> {props}")

    # flash-attention gradient route: a session-level amp knob applied
    # process-wide (the flash custom_vjp has no handle on AmpState) — it
    # sits between the env override and the built-in in
    # flash._resolve_backward's "auto" chain
    from ..contrib.multihead_attn import flash as _flash
    _flash.set_default_backward(props.flash_attn_backward)

    # incoming params must be fp32 unless explicitly allowed
    # (check_params_fp32, _initialize.py:79-116 gated at :170-171 by
    # _amp_state.allow_incoming_model_not_fp32)
    if not allow_incoming_model_not_fp32:
        offending = []
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            dt = getattr(leaf, "dtype", None) or jnp.result_type(leaf)
            if jnp.issubdtype(dt, jnp.floating) and dt != jnp.float32:
                offending.append(jax.tree_util.keystr(path))
        if offending:
            raise RuntimeError(
                "Found param(s) that are not fp32: "
                f"{offending[:8]}{'...' if len(offending) > 8 else ''}. "
                "amp.initialize expects an fp32 model (it applies the "
                "opt_level's cast itself); pass "
                "allow_incoming_model_not_fp32=True if this is intended.")

    # model cast (O2/O3/O5 path; _initialize.py:176-182)
    model_params = params
    ct = props.cast_model_type
    if ct not in (None, False) and jnp.dtype(ct) != jnp.float32:
        model_params = _pt.convert_network(
            params, ct, keep_batchnorm_fp32=bool(props.keep_batchnorm_fp32))
    elif ct not in (None, False):
        model_params = _pt.cast_tree(params, jnp.float32)

    # master weights (_process_optimizer.py:28-90)
    masters = _pt.master_params_from(params) if props.master_weights else None

    # per-loss scalers (_initialize.py:227-231)
    scalers = tuple(
        _scaler.init(props.loss_scale, min_loss_scale=min_loss_scale,
                     max_loss_scale=max_loss_scale)
        for _ in range(num_losses))

    # O1/O4: install per-op autocast patches (amp.py:75)
    if props.patch_functions and props.patch_functions_type is not None:
        _amp.init(patch_type=props.patch_functions_type)

    opt_state = None
    if optimizer is not None:
        target = masters if masters is not None else model_params
        opt_state = optimizer.init(target)
        if (masters is not None and _is_fused_flat(optimizer)
                and getattr(opt_state, "master", None) is not None):
            # flat fast path: the fused state's flat buffer IS the master
            # (authoritative, like the contrib FP16_Optimizer) — a second
            # tree copy would double master memory and force per-step
            # repacking (PERF_NOTES §1).  Gated on the state actually
            # carrying a flat master: sharded optimizers (DistributedFused*)
            # keep per-device `p` shards instead and need the tree masters.
            masters = None

    return AmpState(model_params=model_params, master_params=masters,
                    scalers=scalers, opt_state=opt_state, properties=props,
                    optimizer=optimizer,
                    cast_model_outputs=cast_model_outputs)


def _is_fused_flat(optimizer) -> bool:
    return getattr(optimizer, "impl", None) == "fused"


def _flat_masters_active(amp_state: AmpState) -> bool:
    """True when masters live flat inside the fused optimizer state.
    Gated on ``properties.master_weights``: a fused optimizer's state always
    carries a flat ``master`` buffer, but at master_weights=False levels
    (O0/O1/O3) it holds MODEL-dtype values semantically, not fp32 masters."""
    return (amp_state.master_params is None
            and amp_state.optimizer is not None
            and _is_fused_flat(amp_state.optimizer)
            and bool(amp_state.properties is not None
                     and amp_state.properties.master_weights)
            and getattr(amp_state.opt_state, "master", None) is not None)


def _master_flattener(amp_state: AmpState):
    """Packing plan for THIS state's master layout (fp32 leaves with the
    model tree's structure/shapes).  Re-keys the optimizer's flattener cache
    so a single optimizer object shared across amp states always operates
    with the plan matching the state being stepped."""
    ref = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
        amp_state.model_params)
    return amp_state.optimizer.flattener_for(ref)


def scale_loss(loss, amp_state: AmpState, loss_id: int = 0):
    """Functional ``amp.scale_loss`` (handle.py:16): loss * current scale."""
    return _scaler.scale_loss(amp_state.scalers[loss_id], loss)


def amp_step(amp_state: AmpState, grads, *, loss_id: int = 0, lr=None):
    """The full post-backward pipeline as one pure function:

    unscale grads → overflow check → fused optimizer step on masters →
    skip-step select on overflow → scaler update → model-precision copies.
    Mirrors ``_post_amp_backward`` + patched ``step``
    (_process_optimizer.py:142-202,354-369, handle.py:121-154) with the
    control flow expressed as data (lax/where) so it jits.
    Returns a new AmpState.  (Single-loss special case of
    :func:`amp_step_multi`.)
    """
    return amp_step_multi(amp_state, [(grads, loss_id)], lr=lr)


@annotate_function(name="apex.amp_step")
def amp_step_multi(amp_state: AmpState, grads_and_ids, *, lr=None):
    """Multi-loss pipeline: several backward passes, each scaled by its own
    loss_id scaler, accumulated into ONE optimizer step (the reference's
    num_losses>1 flow — ``scale_loss(loss, opt, loss_id=i)`` per loss, then a
    single ``optimizer.step()``; handle.py:16-158 + scaler.py:161-193's
    ``unscale_with_stashed`` accumulation).

    ``grads_and_ids``: sequence of (grads_pytree, loss_id).  The step is
    skipped if ANY loss overflowed; each scaler updates from its own
    overflow flag.  Returns a new AmpState.
    """
    if amp_state.optimizer is None:
        raise RuntimeError("amp_step_multi requires an optimizer passed to "
                           "initialize()")
    flat = _flat_masters_active(amp_state)
    _tel_events.record_update_path("flat" if flat else "leafwise")
    with annotate("apex.unscale"):
        total32 = None
        finites = {}
        for grads, loss_id in grads_and_ids:
            g32, finite = _scaler.unscale(amp_state.scalers[loss_id], grads)
            finites[loss_id] = (finites[loss_id] & finite
                                if loss_id in finites else finite)
            total32 = g32 if total32 is None else jax.tree_util.tree_map(
                jnp.add, total32, g32)
        all_finite = None
        for f in finites.values():
            all_finite = f if all_finite is None else (all_finite & f)

    scalers = tuple(
        _scaler.update(s, finites[i]) if i in finites else s
        for i, s in enumerate(amp_state.scalers))

    if flat:
        # flat fast path: pack grads once, update the flat master in place,
        # one fused unflatten-with-cast produces the model copy
        opt = amp_state.optimizer
        fl = _master_flattener(amp_state)
        with annotate("apex.opt_update"):
            new_opt_state = opt.step_flat(amp_state.opt_state,
                                          fl.flatten(total32), lr=lr)
            new_opt_state = _scaler.apply_if_finite(
                all_finite, new_opt_state, amp_state.opt_state)
        with annotate("apex.model_copy"):
            model_params = fl.unflatten(new_opt_state.master,
                                        like=amp_state.model_params)
        return amp_state._replace(model_params=model_params,
                                  scalers=scalers,
                                  opt_state=new_opt_state)

    masters = (amp_state.master_params if amp_state.master_params is not None
               else amp_state.model_params)
    with annotate("apex.opt_update"):
        new_masters, new_opt_state = amp_state.optimizer.step(
            amp_state.opt_state, total32, masters, lr=lr)
        new_masters = _scaler.apply_if_finite(all_finite, new_masters,
                                              masters)
        new_opt_state = _scaler.apply_if_finite(all_finite, new_opt_state,
                                                amp_state.opt_state)

    if amp_state.master_params is not None:
        with annotate("apex.model_copy"):
            model_params = _pt.master_to_model(new_masters,
                                               amp_state.model_params)
        return amp_state._replace(model_params=model_params,
                                  master_params=new_masters,
                                  scalers=scalers, opt_state=new_opt_state)
    return amp_state._replace(model_params=new_masters, scalers=scalers,
                              opt_state=new_opt_state)


def add_param_group(amp_state: AmpState, new_params):
    """Extend the trained parameter set mid-run — the ``add_param_group``
    flow (``_process_optimizer.py:469-489`` patched method, tested by the
    reference's ``tests/L0/run_amp/test_add_param_group.py``).

    ``new_params``: fp32 pytree to merge into the model; both the existing
    model tree and ``new_params`` must be dicts with disjoint top-level
    keys (the functional analog of appending a param group).  Returns a new
    AmpState over the merged tree in which

      * existing leaves keep their master values, optimizer moments, and
        step count (the schedule continues),
      * new leaves get preset-consistent casts/masters and zero moments,
      * scaler state carries over unchanged (a mid-run add must not reset
        the dynamic loss scale).

    Works for both impls; the flat fused engine repacks its buffers into
    the merged layout once (a retrace + one-time copy, exactly like the
    reference rebuilding its flat buffers)."""
    props = amp_state.properties
    opt = amp_state.optimizer
    old32 = amp_state.params_for_eval()
    if not (isinstance(old32, dict) and isinstance(new_params, dict)):
        raise TypeError("add_param_group needs dict param pytrees "
                        "(merge = new top-level keys)")
    overlap = set(old32) & set(new_params)
    if overlap:
        raise ValueError(f"new param group re-uses existing keys: "
                         f"{sorted(overlap)}")
    merged32 = {**old32, **new_params}

    fresh = initialize(
        merged32, opt, opt_level=props.opt_level,
        num_losses=len(amp_state.scalers), verbosity=0,
        # forward EVERY stored property, not just the preset name — a user
        # override like cast_model_type=bf16 on O2 must survive the re-init
        cast_model_type=props.cast_model_type,
        patch_functions=props.patch_functions,
        keep_batchnorm_fp32=props.keep_batchnorm_fp32,
        master_weights=props.master_weights,
        loss_scale=props.loss_scale,
        cast_model_outputs=amp_state.cast_model_outputs)

    new_opt_state = fresh.opt_state
    if amp_state.opt_state is not None and new_opt_state is not None:
        if _is_fused_flat(opt):
            new_opt_state = _migrate_flat_state(
                amp_state, fresh, old32, merged32)
        else:
            merged_fields = {}
            for field in new_opt_state._fields:
                old_v = getattr(amp_state.opt_state, field)
                fresh_v = getattr(new_opt_state, field)
                if isinstance(old_v, dict) and isinstance(fresh_v, dict) \
                        and set(old_v) <= set(fresh_v):
                    merged_fields[field] = {**fresh_v, **old_v}
                elif (hasattr(old_v, "shape") and hasattr(fresh_v, "shape")
                      and old_v.shape == fresh_v.shape):
                    merged_fields[field] = old_v        # count-style scalars
                else:
                    merged_fields[field] = fresh_v
            new_opt_state = type(new_opt_state)(**merged_fields)

    return fresh._replace(opt_state=new_opt_state,
                          scalers=amp_state.scalers)


def _migrate_flat_state(amp_state, fresh, old32, merged32):
    """Scatter the old flat buffers (m/v/master/...) into the merged
    layout: unflatten per the old packing plan, overlay onto the fresh
    tree, re-flatten per the new plan.  Non-flat fields (count) carry."""
    opt = amp_state.optimizer
    old_fl = opt.flattener_for(jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), old32))
    old_total = old_fl.total
    # capture old trees FIRST: flattener_for holds only one cached plan
    old_trees = {}
    for field in amp_state.opt_state._fields:
        v = getattr(amp_state.opt_state, field)
        if hasattr(v, "ndim") and getattr(v, "ndim", 0) == 1 \
                and v.shape[0] == old_total:
            old_trees[field] = old_fl.unflatten(v, dtype=jnp.float32)
    new_fl = opt.flattener_for(jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), merged32))
    merged_fields = {}
    for field in fresh.opt_state._fields:
        fresh_v = getattr(fresh.opt_state, field)
        old_v = getattr(amp_state.opt_state, field)
        if field in old_trees and hasattr(fresh_v, "ndim") \
                and fresh_v.ndim == 1 and fresh_v.shape[0] == new_fl.total:
            fresh_tree = new_fl.unflatten(fresh_v, dtype=jnp.float32)
            merged_fields[field] = new_fl.flatten(
                {**fresh_tree, **old_trees[field]})
        elif (hasattr(old_v, "shape") and hasattr(fresh_v, "shape")
              and old_v.shape == fresh_v.shape):
            merged_fields[field] = old_v                # count-style scalars
        else:
            merged_fields[field] = fresh_v
    return type(fresh.opt_state)(**merged_fields)


def master_params(amp_state: AmpState):
    """Iterate master (fp32) params — ``amp.master_params`` (_amp_state.py:58-68)."""
    if _flat_masters_active(amp_state):
        return jax.tree_util.tree_leaves(
            _master_flattener(amp_state).unflatten(amp_state.opt_state.master))
    src = (amp_state.master_params if amp_state.master_params is not None
           else amp_state.model_params)
    return jax.tree_util.tree_leaves(src)


def state_dict(amp_state: AmpState) -> dict:
    """Serialize all scaler states (``amp.state_dict``, frontend.py:428-442)."""
    return {f"loss_scaler{i}": _scaler.state_dict(s)
            for i, s in enumerate(amp_state.scalers)}


def load_state_dict(amp_state: AmpState, d: dict) -> AmpState:
    """Restore scaler states (frontend.py:444-467)."""
    if len(d) != len(amp_state.scalers):
        print(f"Warning: loading state with {len(d)} scalers into "
              f"{len(amp_state.scalers)} (frontend.py:449 semantics)")
    scalers = list(amp_state.scalers)
    for i in range(min(len(d), len(scalers))):
        scalers[i] = _scaler.load_state_dict(d[f"loss_scaler{i}"])
    return amp_state._replace(scalers=tuple(scalers))
