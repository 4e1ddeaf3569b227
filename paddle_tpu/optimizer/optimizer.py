"""Optimizer base + the standard optimizers.

Reference analogue: python/paddle/optimizer/optimizer.py:50 (base Optimizer,
minimize:1120, step:1185) and the per-optimizer phi kernels
(paddle/phi/kernels/{sgd,adam,adamw,momentum,...}_kernel.h).

Design: every optimizer defines a *pure* per-parameter update rule
`_update(p, g, lr, state) -> (new_p, new_state)` (arrays in, arrays out).
Eager `step()` applies it through one fused jitted call per parameter; the
compiled training-step path (paddle_tpu.jit) calls the same rule inside the
whole-program trace, so eager and jit share optimizer math exactly.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flags as _flags
from ..core import lazy as _lazy
from ..core.dispatch import _count_program, no_grad
from ..core.tensor import Tensor
from .lr import LRScheduler

_jit_update_cache: Dict = {}


def make_fused_update(opt, params, sentinel=False, telemetry=False):
    """Pure multi-tensor update applier `(p_vals, g_vals, lr, states) ->
    (new_ps, new_states)` over `opt`'s rule for `params`.

    The ONE definition of the traced optimizer math shared by the eager
    fused step (`_apply_fused`), the whole-step capture trace
    (core/lazy.py `_build_captured_step`) and every compiled builder
    (jit/step.py `make_step_fn`, the pipelined step's two appliers): same
    rule, same static global + per-param hyper merge (e.g. AdamW's
    apply_decay_param_fun excluding biases from weight decay), same
    grad-dtype cast. The rule is bound to a
    bare shim carrying just `_weight_decay` — NOT the live optimizer — so
    callers can cache the (jitted) closure without pinning the instance
    and its accumulators.

    With `sentinel=True` (FLAGS_numeric_rescue, paddle.resilience) the
    applier returns a third output — `any(~isfinite(g))` over every grad —
    and where-gates the whole update on it: a non-finite step returns the
    ORIGINAL params and state. The scan and the gate are folded into the
    same traced program, so rescue adds zero program launches.

    With `telemetry=True` (FLAGS_telemetry, paddle.profiler.attribution)
    the applier appends one MORE output — a stacked `(n_params, 3)` f32
    vector of per-parameter sums of squares: grad², param², and
    (new_p − p)² — the fused-numerics telemetry the attribution layer
    reduces to per-group grad-norm / param-norm / update-ratio on the
    host. Same mechanism as the sentinel: extra outputs of the SAME
    traced program, zero extra launches, and the update chain itself is
    untouched, so step numerics stay bitwise-identical to telemetry-off.
    Output order is always (new_ps, new_states[, bad][, telemetry]).

    With FLAGS_pallas_fused_update (on TPU, or under the interpret flag),
    eligible parameters route through the hand-written Pallas kernel
    (ops/pallas/fused_update.py): the whole elementwise update chain — and
    the sentinel gate — runs as one VMEM pass per buffer. Ineligible
    params (unsupported rule, dtype, or tile size) keep the lax rule in
    the SAME traced program, so the callers' 1/3-program arithmetic never
    changes. The enablement is part of both compile-cache keys
    (_apply_fused's and the capture controller's), so flipping the flag
    retraces instead of replaying a stale program."""
    rule = type(opt)._update
    hypers = [dict(opt._hyper(), **opt._per_param_hyper(p)) for p in params]
    ctx = object.__new__(type(opt))
    ctx._weight_decay = opt._weight_decay
    kind = None
    if _flags.flag("pallas_fused_update"):
        # imported only where the flag asks for it: the default path of
        # every step builder stays clear of Pallas
        from ..ops.pallas import fused_update as _pfu

        kind = _pfu.rule_kind(type(opt)) if _pfu.enabled() else None

    def apply_update(p_vals, g_vals, lr, states):
        bad = None
        if sentinel:
            bad = jnp.asarray(False)
            for gv in g_vals:
                bad = bad | jnp.any(~jnp.isfinite(gv))
        new_ps, new_sts = [], []
        tele_rows = []
        for pv, gv, st, hy in zip(p_vals, g_vals, states, hypers):
            if gv.dtype != pv.dtype:
                gv = gv.astype(pv.dtype)
            if kind is not None and _pfu.supported(kind, pv, gv, st):
                # sentinel gating happens IN-KERNEL (bad rides in SMEM) —
                # these outputs must not be re-gated below
                np_, nst = _pfu.param_update(
                    kind, pv, gv, lr, st, hy,
                    wd=ctx._weight_decay, bad=bad,
                )
            else:
                np_, nst = rule(ctx, pv, gv, lr, st, **hy)
                if bad is not None:
                    np_ = jnp.where(bad, pv, np_)
                    nst = jax.tree_util.tree_map(
                        lambda o, n: jnp.where(bad, o, n), st, nst
                    )
            if telemetry:
                # fused numerics telemetry: per-param sums of squares of
                # the (post-cast) grad, the param, and the APPLIED update
                # (post-gate, so a rescued step reports a zero update) —
                # independent extra outputs, the update chain is untouched
                f32 = jnp.float32
                tele_rows.append(jnp.stack([
                    jnp.sum(jnp.square(gv.astype(f32))),
                    jnp.sum(jnp.square(pv.astype(f32))),
                    jnp.sum(jnp.square((np_ - pv).astype(f32))),
                ]))
            new_ps.append(np_)
            new_sts.append(nst)
        out = (new_ps, new_sts)
        if sentinel:
            out = out + (bad,)
        if telemetry:
            out = out + (jnp.stack(tele_rows),)
        return out

    return apply_update


class Optimizer:
    _update_has_state = True

    def __init__(
        self,
        learning_rate=0.001,
        parameters=None,
        weight_decay=None,
        grad_clip=None,
        name=None,
        multi_precision=False,
    ):
        self._lr = learning_rate
        self._parameters = list(parameters) if parameters is not None else None
        self._weight_decay = self._parse_wd(weight_decay)
        self._grad_clip = grad_clip
        # per-parameter optimizer state: id(param) -> dict[str, jax.Array]
        self._accumulators: Dict[int, Dict[str, jax.Array]] = {}
        self._step_count = 0

    @staticmethod
    def _parse_wd(weight_decay):
        if weight_decay is None:
            return 0.0
        if isinstance(weight_decay, float):
            return weight_decay
        # L2Decay regularizer object
        coeff = getattr(weight_decay, "_coeff", None)
        return float(coeff) if coeff is not None else float(weight_decay)

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, value):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError(
                "optimizer's learning rate is an LRScheduler; call scheduler.step()"
            )
        self._lr = float(value)

    @property
    def _learning_rate(self):
        return self._lr

    # -- state rules (override per optimizer) --------------------------------
    def _create_state(self, p: Tensor) -> Dict[str, jax.Array]:
        return {}

    def _update(self, p, g, lr, state, **hyper):
        raise NotImplementedError

    def _hyper(self) -> Dict:
        """Static hyper-parameters baked into the jitted update."""
        return {}

    def _per_param_hyper(self, p: Tensor) -> Dict:
        """Static per-parameter hyper overrides (e.g. no-decay params) —
        consumed by the compiled whole-step path (paddle_tpu.jit)."""
        return {}

    # -- main API ------------------------------------------------------------
    @no_grad()
    def step(self):
        """reference: optimizer.py:1185 step. The reference launches one phi
        optimizer kernel per param; here ALL param updates run as ONE cached
        jitted XLA program (the merged_adam/multi_tensor path the reference
        gates behind use_multi_tensor), so eager training pays a single
        dispatch per step instead of one per parameter."""
        # whole-step capture boundary (FLAGS_eager_step_capture): a deferred
        # backward resolves here as ONE donated XLA program covering forward
        # + backward + this update. Otherwise this is the ordinary lazy-
        # dispatch materialization point — grads (and lazily-created params)
        # are flushed concrete before the fused jitted update reads them —
        # plus step-signature observation for the capture controller.
        from ..resilience import runtime as _rrt

        # host-offload boundary (optimizer/offload.py): start the H2D
        # prefetch of parked accumulator groups now, overlapped behind the
        # step's own dispatch; step_end() below books the measured figures
        # and enqueues the next D2H sweep
        sched = getattr(self, "_offload_sched", None)
        if sched is not None:
            sched.step_begin()
        try:
            if _lazy.step_capture_step(self):
                self._step_count += 1
                return
            params_grads = [
                (p, p.grad)
                for p in self._param_list()
                if not p.stop_gradient and p.grad is not None
            ]
            if self._grad_clip is not None:
                params_grads = self._grad_clip(params_grads)
            self._step_count += 1
            if params_grads:
                self._apply_fused(params_grads)
        finally:
            if sched is not None:
                sched.step_end()
            # resilience step boundary: advances the fault-injection step
            # counter and the degradation ladder's cooldown clocks
            _rrt.on_step_end()

    def _apply_fused(self, params_grads):
        from ..core import dispatch as _dispatch
        from ..resilience import faults as _faults
        from ..resilience import rescue as _rescue
        from ..resilience import runtime as _rrt

        params = [p for p, _ in params_grads]
        g_vals = [
            (_lazy.materialize(g._value) if isinstance(g, Tensor) else g)
            for _, g in params_grads
        ]
        # chaos harness: a `nan:grads` clause poisons the first gradient
        # this step (the numeric-rescue sentinel must catch it in-program)
        plan = _faults.active_plan()
        if plan is not None and g_vals and plan.nan_fires(
            "grads", _faults.current_step()
        ):
            _dispatch._counters["injected_faults"] += 1
            g_vals = list(g_vals)
            g_vals[0] = jnp.full_like(g_vals[0], jnp.nan)
        sentinel = _rescue.active()
        from ..profiler import attribution as _attribution

        telemetry = _attribution.telemetry_active()
        sched = getattr(self, "_offload_sched", None)
        if sched is not None:
            # join the prefetch: any accumulator still parked on the host
            # comes back NOW, and the wait is booked as blocked time (the
            # overhead figure the scheduler tunes against)
            sched.ensure_resident(self, params)
        states = []
        for p in params:
            st = self._accumulators.get(id(p))
            if st is None:
                st = self._create_state(p)
                self._accumulators[id(p)] = st
            states.append(st)
        # key covers everything the traced update reads besides its arrays:
        # rule identity, global + per-param statics, and array shapes/dtypes
        # (jit would retrace on those anyway; keying here keeps one wrapper
        # per configuration instead of leaking one per optimizer instance).
        # The key is memoized per (param identity, shapes/dtypes) — rebuilding
        # it each step costs more than the whole host-side dispatch.
        per_hypers = tuple(
            tuple(sorted(self._per_param_hyper(p).items())) for p in params
        )
        # the Pallas fused-update enablement changes the traced program —
        # it must key the cache so flipping the flag retraces
        pallas = (
            bool(_flags.flag("pallas_fused_update")),
            bool(_flags.flag("pallas_update_interpret")),
        )
        sig = (
            tuple(sorted(self._hyper().items())),
            per_hypers,
            self._weight_decay,
            sentinel,
            telemetry,
            pallas,
            tuple(
                (id(p), p._value.shape, p._value.dtype, g.dtype)
                for p, g in zip(params, g_vals)
            ),
        )
        memo = getattr(self, "_fused_key_memo", None)
        if memo is not None and memo[0] == sig:
            key = memo[1]
        else:
            key = (
                type(self),
                tuple(sorted(self._hyper().items())),
                per_hypers,
                self._weight_decay,
                sentinel,
                telemetry,
                pallas,
                tuple(
                    (p._value.shape, str(p._value.dtype), str(g.dtype))
                    for p, g in zip(params, g_vals)
                ),
            )
            self._fused_key_memo = (sig, key)
        fn = _jit_update_cache.get(key)
        if fn is None:
            # make_fused_update binds a bare weight-decay shim, NOT `self`:
            # this cache is global and capturing the instance would pin its
            # accumulators (potentially hundreds of MB of moments) forever
            fn = jax.jit(make_fused_update(self, params, sentinel=sentinel,
                                           telemetry=telemetry))
            _jit_update_cache[key] = fn
        p_vals = [p._value for p in params]
        lr = jnp.asarray(self.get_lr(), dtype=jnp.float32)
        out = _rrt.execute("optimizer", lambda: fn(p_vals, g_vals, lr, states))
        new_ps, new_sts = out[0], out[1]
        extra = list(out[2:])
        bad = extra.pop(0) if sentinel else None
        tele = extra.pop(0) if telemetry else None
        _count_program("optimizer")
        for p, npv, nst in zip(params, new_ps, new_sts):
            p._value = npv
            self._accumulators[id(p)] = nst
        if tele is not None:
            # fused telemetry host-read BEFORE the rescue policy, so a
            # rescue postmortem's tail already carries the spike event
            _attribution.record_telemetry(
                _attribution.group_names(params), tele)
        if bad is not None:
            # host-read of the fused sentinel (same program's output —
            # no extra launch); applies skip / lr_backoff / abort
            _rescue.handle_sentinel(self, bad)

    def _param_list(self) -> List[Tensor]:
        if self._parameters is None:
            raise ValueError(
                "optimizer was created without a parameter list (static-graph "
                "mode is driven through minimize())"
            )
        return self._parameters

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        """reference: optimizer.py:1120 — backward + apply."""
        loss.backward()
        self.step()
        return None, None

    @no_grad()
    def clear_grad(self, set_to_zero=False):
        for p in self._param_list():
            p.clear_grad()

    clear_gradients = clear_grad

    # -- checkpoint ----------------------------------------------------------
    def state_dict(self):
        # a compiled (pipelined) step may hold authoritative stacked moments;
        # let it write them back into _accumulators first
        sync = getattr(self, "_lazy_state_sync", None)
        if sync is not None:
            sync()
        out = {"_step_count": self._step_count}
        params = self._param_list()
        for i, p in enumerate(params):
            st = self._accumulators.get(id(p))
            if st:
                for k, v in st.items():
                    out[f"{p.name or i}.{k}"] = Tensor(v)
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state_dict):
        self._step_count = int(state_dict.get("_step_count", 0))
        if "LR_Scheduler" in state_dict and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
        params = self._param_list()
        for i, p in enumerate(params):
            prefix = f"{p.name or i}."
            st = {}
            for k, v in state_dict.items():
                if isinstance(k, str) and k.startswith(prefix):
                    val = v._value if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
                    st[k[len(prefix):]] = val
            if st:
                cur = self._accumulators.get(id(p)) or self._create_state(p)
                cur.update(st)
                self._accumulators[id(p)] = cur

    set_dict = set_state_dict

    def _apply_weight_decay_l2(self, g, p):
        if self._weight_decay:
            return g + self._weight_decay * p
        return g


class SGD(Optimizer):
    """reference: phi/kernels/sgd_kernel.h."""

    def _update(self, p, g, lr, state):
        g = self._apply_weight_decay_l2(g, p)
        return p - lr.astype(p.dtype) * g, state


class Momentum(Optimizer):
    """reference: phi momentum_kernel; use_nesterov supported."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _hyper(self):
        return {"mu": self._momentum, "nesterov": self._nesterov}

    def _create_state(self, p):
        return {"velocity": jnp.zeros_like(p._value)}

    def _update(self, p, g, lr, state, *, mu, nesterov):
        g = self._apply_weight_decay_l2(g, p)
        v = mu * state["velocity"] + g
        if nesterov:
            step = g + mu * v
        else:
            step = v
        return p - lr.astype(p.dtype) * step, {"velocity": v}


class Adam(Optimizer):
    """reference: phi adam_kernel; bias-corrected like the reference
    (beta1/beta2 pow accumulators)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _hyper(self):
        return {"b1": self._beta1, "b2": self._beta2, "eps": self._epsilon}

    def _create_state(self, p):
        return {
            "moment1": jnp.zeros_like(p._value),
            "moment2": jnp.zeros_like(p._value),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }

    def _update(self, p, g, lr, state, *, b1, b2, eps):
        g = self._apply_weight_decay_l2(g, p)
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * jnp.square(g)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        lr_t = (lr * jnp.sqrt(1 - b2p) / (1 - b1p)).astype(p.dtype)
        new_p = p - lr_t * m / (jnp.sqrt(v) + eps)
        return new_p, {
            "moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p,
        }


class AdamW(Adam):
    """reference: phi adamw_kernel — decoupled weight decay."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip)
        self._wd_coeff = float(weight_decay) if not hasattr(weight_decay, "_coeff") else float(weight_decay._coeff)
        self._apply_decay_param_fun = apply_decay_param_fun

    def _hyper(self):
        return {"b1": self._beta1, "b2": self._beta2, "eps": self._epsilon,
                "wd": self._wd_coeff}

    @no_grad()
    def _update(self, p, g, lr, state, *, b1, b2, eps, wd):
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * jnp.square(g)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        lr_t = (lr * jnp.sqrt(1 - b2p) / (1 - b1p)).astype(p.dtype)
        new_p = p * (1.0 - (lr * wd).astype(p.dtype)) - lr_t * m / (jnp.sqrt(v) + eps)
        return new_p, {
            "moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p,
        }

    def _per_param_hyper(self, p):
        # single decay-exclusion path, merged identically by the eager
        # _apply_one and the compiled train step
        if self._apply_decay_param_fun is not None and not self._apply_decay_param_fun(
            p.name
        ):
            return {"wd": 0.0}
        return {}


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _hyper(self):
        return {"b1": self._beta1, "b2": self._beta2, "eps": self._epsilon}

    def _create_state(self, p):
        return {
            "moment": jnp.zeros_like(p._value),
            "inf_norm": jnp.zeros_like(p._value),
            "beta1_pow": jnp.ones((), jnp.float32),
        }

    def _update(self, p, g, lr, state, *, b1, b2, eps):
        g = self._apply_weight_decay_l2(g, p)
        m = b1 * state["moment"] + (1 - b1) * g
        u = jnp.maximum(b2 * state["inf_norm"], jnp.abs(g))
        b1p = state["beta1_pow"] * b1
        new_p = p - (lr / (1 - b1p)).astype(p.dtype) * m / (u + eps)
        return new_p, {"moment": m, "inf_norm": u, "beta1_pow": b1p}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _hyper(self):
        return {"eps": self._epsilon}

    def _create_state(self, p):
        return {"moment": jnp.full_like(p._value, self._init_acc)}

    def _update(self, p, g, lr, state, *, eps):
        g = self._apply_weight_decay_l2(g, p)
        acc = state["moment"] + jnp.square(g)
        return p - lr.astype(p.dtype) * g / (jnp.sqrt(acc) + eps), {"moment": acc}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon, self._rho = epsilon, rho

    def _hyper(self):
        return {"eps": self._epsilon, "rho": self._rho}

    def _create_state(self, p):
        return {
            "avg_squared_grad": jnp.zeros_like(p._value),
            "avg_squared_update": jnp.zeros_like(p._value),
        }

    def _update(self, p, g, lr, state, *, eps, rho):
        g = self._apply_weight_decay_l2(g, p)
        asg = rho * state["avg_squared_grad"] + (1 - rho) * jnp.square(g)
        update = (
            jnp.sqrt(state["avg_squared_update"] + eps) / jnp.sqrt(asg + eps) * g
        )
        asu = rho * state["avg_squared_update"] + (1 - rho) * jnp.square(update)
        return p - lr.astype(p.dtype) * update, {
            "avg_squared_grad": asg, "avg_squared_update": asu,
        }


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _hyper(self):
        return {"rho": self._rho, "eps": self._epsilon,
                "mu": self._momentum, "centered": self._centered}

    def _create_state(self, p):
        return {
            "mean_square": jnp.zeros_like(p._value),
            "mean_grad": jnp.zeros_like(p._value),
            "momentum": jnp.zeros_like(p._value),
        }

    def _update(self, p, g, lr, state, *, rho, eps, mu, centered):
        g = self._apply_weight_decay_l2(g, p)
        ms = rho * state["mean_square"] + (1 - rho) * jnp.square(g)
        if centered:
            mg = rho * state["mean_grad"] + (1 - rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + eps)
        else:
            mg = state["mean_grad"]
            denom = jnp.sqrt(ms + eps)
        mom = mu * state["momentum"] + lr.astype(p.dtype) * g / denom
        return p - mom, {"mean_square": ms, "mean_grad": mg, "momentum": mom}


class Lamb(Optimizer):
    """reference: operators/optimizers/lamb_op + LambOptimizer meta-optimizer."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _hyper(self):
        return {"b1": self._beta1, "b2": self._beta2, "eps": self._epsilon,
                "wd": self._wd}

    def _create_state(self, p):
        return {
            "moment1": jnp.zeros_like(p._value),
            "moment2": jnp.zeros_like(p._value),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }

    def _update(self, p, g, lr, state, *, b1, b2, eps, wd):
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * jnp.square(g)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where(
            (w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0
        ).astype(p.dtype)
        return p - lr.astype(p.dtype) * trust * r, {
            "moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p,
        }


class Lars(Optimizer):
    """LARS — layer-wise adaptive rate scaling for large-batch SGD.

    reference: operators/optimizers/lars_momentum_op.cc + the
    LarsOptimizer meta-optimizer (fleet/meta_optimizers/lars_optimizer.py):
    local_lr = lr * coeff * ||w|| / (||g|| + lambda*||w|| + eps), momentum
    applied on the rescaled gradient."""

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 lars_coeff=0.001, lars_weight_decay=0.0005,
                 parameters=None, grad_clip=None, exclude_from_weight_decay=None,
                 epsilon=0.0, name=None, multi_precision=False):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._momentum = momentum
        self._coeff = lars_coeff
        self._wd = lars_weight_decay
        self._eps = epsilon
        # name fragments excluded from weight decay (reference: lars
        # meta-optimizer's exclude_from_weight_decay list — biases/norms)
        self._exclude = list(exclude_from_weight_decay or [])

    def _hyper(self):
        return {"mu": self._momentum, "coeff": self._coeff, "wd": self._wd,
                "eps": self._eps}

    def _per_param_hyper(self, p):
        name = getattr(p, "name", "") or ""
        if any(frag in name for frag in self._exclude):
            return {"wd": 0.0}
        return {}

    def _create_state(self, p):
        return {"velocity": jnp.zeros_like(p._value)}

    def _update(self, p, g, lr, state, *, mu, coeff, wd, eps):
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            coeff * w_norm / (g_norm + wd * w_norm + eps),
            1.0,
        ).astype(p.dtype)
        step = g + wd * p
        v = mu * state["velocity"] + (lr.astype(p.dtype) * local_lr) * step
        return p - v, {"velocity": v}
