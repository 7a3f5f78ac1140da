"""FusedLAMB — layer-wise adaptive large-batch optimizer.

Re-design of ``apex/optimizers/fused_lamb.py:4-214`` (kernels
``csrc/multi_tensor_lamb.cu`` Stage1/Stage2): global-grad-norm clipping
(``max_grad_norm``), per-tensor trust ratios, AdamW-style decoupled decay.
The CUDA two-stage structure maps to two passes over a tensor: stage 1
(m / v + step direction) yields the two norms of the trust ratio, stage 2
applies it.  Two layouts of ONE mathematics (:meth:`FusedLAMB._direction`,
:meth:`FusedLAMB._trust_ratio`): leaf by leaf in the leaves' own layouts
(``impl="xla"``, a replicated update) and over flat buffers with the
flattener's static segment reductions (``impl="fused"``: ``step_flat``, and
``step_flat_shard`` where a replica holds a slice).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ._base import FusedOptimizer, resolve, _f32, global_l2norm
from ..multi_tensor_apply import kernels
from ..multi_tensor_apply.flattener import LANE


class FusedLAMBState(NamedTuple):
    count: jnp.ndarray
    m: Any
    v: Any
    master: Any = None   # fused impl: flat fp32 master params (authoritative)
    #                      per-leaf: None — m and v are trees, the masters the
    #                      caller's (amp's ``master_params``)


class FusedLAMB(FusedOptimizer):
    #: per-tensor trust ratios + the global-grad-norm clip span shards:
    #: the sharded path needs the cross-shard override below
    elementwise_flat_update = False
    leafwise_state_dtype = True

    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-6, weight_decay=0.01, amsgrad=False,
                 adam_w_mode=True, grad_averaging=True, set_grad_none=True,
                 max_grad_norm=1.0, use_nvlamb=False, impl="xla",
                 state_dtype=None):
        super().__init__(lr, weight_decay, impl, state_dtype)
        if amsgrad:
            raise RuntimeError("FusedLAMB does not support AMSGrad "
                               "(fused_lamb.py:79).")
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.grad_averaging = grad_averaging
        self.max_grad_norm = max_grad_norm
        # use_nvlamb: apply trust ratio even when wd == 0 (fused_lamb.py:70)
        self.use_nvlamb = use_nvlamb

    def init(self, params) -> FusedLAMBState:
        if self.impl == "fused":
            fl = self.flattener_for(params)
            # m and v must be distinct buffers: a shared array donated twice
            # (jit donate_argnums) is an aliasing error on the TPU backend
            return FusedLAMBState(jnp.zeros((), jnp.int32),
                                  jnp.zeros((fl.total,), self.state_dtype),
                                  jnp.zeros((fl.total,), self.state_dtype),
                                  fl.flatten(params))
        # per-leaf: moments shaped like the parameters, each in its leaf's
        # own layout (two trees: m and v must not share buffers either)
        zeros = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, self.state_dtype), params)
        return FusedLAMBState(jnp.zeros((), jnp.int32), zeros(), zeros())

    def _clip_coeff(self, gnorm):
        """1/max(1, gnorm/max_grad_norm) — the global clip folded into stage 1
        (multi_tensor_lamb.cu:41, clip_global_grad_norm)."""
        if self.max_grad_norm is None or self.max_grad_norm <= 0:
            return jnp.ones((), jnp.float32)
        return 1.0 / jnp.maximum(1.0, gnorm / self.max_grad_norm)

    def _prep(self, state, lr):
        count = state.count + 1
        lr = jnp.asarray(resolve(lr if lr is not None else self.lr, count),
                         jnp.float32)
        b1, b2 = self.beta1, self.beta2
        if self.bias_correction:
            t = count.astype(jnp.float32)
            rc1 = 1.0 / (1.0 - b1 ** t)
            rc2 = 1.0 / (1.0 - b2 ** t)
        else:
            rc1 = rc2 = jnp.ones((), jnp.float32)
        return count, lr, rc1, rc2

    def _direction(self, g, p, m, v, rc1, rc2):
        """``(m, v, u)`` of the ``LAMBStage1Functor``: the moments and the
        update direction from the unscaled + clipped float32 gradient ``g``,
        the float32 weights ``p`` and the stored moments.  ONE function for
        the per-leaf step and the flat chain (full or shard-length), so an
        update-math fix can never miss a twin; moments may be stored narrow
        (``state_dtype``): upcast here, cast back only at store."""
        wd = jnp.asarray(self.weight_decay, jnp.float32)
        b1, b2 = self.beta1, self.beta2
        beta3 = 1.0 - b1 if self.grad_averaging else 1.0
        if not self.adam_w_mode:
            g = g + wd * p
        m = b1 * _f32(m) + beta3 * g
        v = b2 * _f32(v) + (1.0 - b2) * g * g
        u = (m * rc1) / (jnp.sqrt(v * rc2) + self.eps)
        if self.adam_w_mode:
            u = u + wd * p
        return m, v, u

    def _trust_ratio(self, w_sumsq, u_sumsq):
        """Per-tensor ``‖w‖ / ‖u‖`` (``LAMBStage2Functor``,
        multi_tensor_lamb.cu:234); 1 where either norm is 0 and, unless
        ``use_nvlamb``, where nothing decays."""
        w_norm, u_norm = jnp.sqrt(w_sumsq), jnp.sqrt(u_sumsq)
        ratio = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        if not self.use_nvlamb and self.weight_decay == 0.0:
            ratio = jnp.ones_like(ratio)
        return ratio

    def step(self, state, grads, params, *, scale=1.0, lr=None):
        if self.impl == "fused":
            fl = self.flattener_for(params)
            new_state = self.step_flat(state, fl.flatten(grads), scale=scale,
                                       lr=lr)
            return fl.unflatten(new_state.master), new_state

        # leaf by leaf, each in the layout it has: ``scale`` and the clip
        # are ONE scalar, and a leaf costs two passes — its two norms from
        # one read of g, p, m, v, then the apply.  Under jit XLA fuses the
        # neighbours into them: amp's unscale before (the gradient is read
        # as the backward left it; no float32 copy of it is written), amp's
        # skip select and model-precision copy after
        count, lr, rc1, rc2 = self._prep(state, lr)
        inv_scale = 1.0 / jnp.asarray(scale, jnp.float32)
        # global grad norm over *unscaled* grads (fused_lamb.py:123-135)
        gnorm = global_l2norm(grads) * inv_scale
        coeff = inv_scale * self._clip_coeff(gnorm)

        def upd(g, p, m, v):
            p32 = _f32(p)
            m_new, v_new, u = self._direction(_f32(g) * coeff, p32, m, v,
                                              rc1, rc2)
            ratio = self._trust_ratio(jnp.sum(p32 * p32), jnp.sum(u * u))
            return ((p32 - lr * ratio * u).astype(p.dtype),
                    self._store_moment(m_new), self._store_moment(v_new))

        out = jax.tree_util.tree_map(upd, grads, params, state.m, state.v)
        is_t = lambda x: isinstance(x, tuple)
        new_params = jax.tree_util.tree_map(lambda o: o[0], out, is_leaf=is_t)
        new_m = jax.tree_util.tree_map(lambda o: o[1], out, is_leaf=is_t)
        new_v = jax.tree_util.tree_map(lambda o: o[2], out, is_leaf=is_t)
        return new_params, FusedLAMBState(count, new_m, new_v)

    def step_flat(self, state, flat_grads, *, scale=1.0, lr=None):
        """Flat-native two-stage LAMB over the permanently-flat buffers.

        Stage 1 (the ``LAMBStage1Functor`` math) runs as one XLA elementwise
        fusion; per-tensor ``(w, u)`` norms come from the flattener's static
        row-range reductions; stage 2 applies the trust-ratio-scaled update
        with the per-tensor ratio broadcast by row (``LAMBStage2Functor``).
        The global-grad-norm clip uses the Pallas l2norm kernel (measured
        faster than the XLA reduce; PERF_NOTES.md)."""
        count, lr, rc1, rc2 = self._prep(state, lr)
        inv_scale = 1.0 / jnp.asarray(scale, jnp.float32)
        # l2norm is homogeneous (||c*x|| = c*||x||, inv_scale > 0): norm
        # the RAW grads (the kernel reads them in their original dtype —
        # half the bandwidth for bf16 grads) and fold unscale+clip into
        # ONE scalar applied inside the stage-1 fusion.  vs the round-3
        # form (materialize g = grads*inv_scale, then kernel-read it)
        # this saves a full write+read of the flat buffer per step
        # (~2.7 GB at 334M params).
        gnorm = kernels.multi_tensor_l2norm(flat_grads) * inv_scale
        g = flat_grads.astype(jnp.float32) * (
            inv_scale * self._clip_coeff(gnorm))
        return self._flat_update(state, g, self.flattener, count, lr,
                                 rc1, rc2)

    def step_flat_shard(self, state, g_shard, *, shard, scale=1.0, lr=None):
        """Sharded two-stage LAMB (``parallel.weight_update``): the same
        chain as :meth:`step_flat` on this replica's 1/N slice — only
        the reduction providers differ: the global-grad-norm clip and
        the per-tensor ``(w, u)`` norms span shards, so they come from
        the shard context's psum'd partial reductions (the
        ``DistributedFusedLAMB`` stage-2 scheme)."""
        count, lr, rc1, rc2 = self._prep(state, lr)
        inv_scale = 1.0 / jnp.asarray(scale, jnp.float32)
        gnorm = jnp.sqrt(shard.global_sumsq(g_shard)) * inv_scale
        g = g_shard.astype(jnp.float32) * (
            inv_scale * self._clip_coeff(gnorm))
        return self._flat_update(state, g, shard, count, lr, rc1, rc2)

    def _flat_update(self, state, g, reducer, count, lr, rc1, rc2):
        """Stage 1+2 over flat buffers (full or shard-length): ``g`` is
        the unscaled+clipped fp32 gradient buffer matching the state's
        flat fields; ``reducer`` provides
        ``per_tensor_sumsq``/``broadcast_rows`` spanning the whole
        model — the ``TreeFlattener``'s static row-range reductions or
        the ``ShardContext``'s psum'd partials.  ONE chain, so an
        update-math fix can never miss the sharded twin."""
        p = state.master
        m, v, u = self._direction(g, p, state.m, state.v, rc1, rc2)

        # stage 2: per-tensor trust ratios via the reducer
        ratio = self._trust_ratio(reducer.per_tensor_sumsq(p),
                                  reducer.per_tensor_sumsq(u))
        ratio_rows = reducer.broadcast_rows(ratio)            # (rows,)
        p_new = (p.reshape(-1, LANE)
                 - lr * ratio_rows[:, None] * u.reshape(-1, LANE))
        return FusedLAMBState(count, self._store_moment(m),
                              self._store_moment(v),
                              p_new.reshape(p.shape))
