"""CompiledTrainStep — the whole-step jitted trainer (the TPU perf path).

Reference parity: this replaces the reference's executor+CINN "static graph
training" mode (SURVEY.md §7 stage 4). One jax.jit covers forward, backward,
gradient clipping, weight decay, and the optimizer update, with parameter
and optimizer-state buffers donated — XLA fuses the lot and the host only
dispatches one executable per step. Loss scaling / AMP run inside the trace.

Works with the imperative Layer/Optimizer objects: parameters and optimizer
accumulators are pulled into pytrees, the pure step runs, and the results
are written back — so .state_dict(), checkpoints, and eager inspection all
keep working between steps.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from .. import chaos as _chaos
from ..core import random as random_mod
from ..core import tape
from ..core.tensor import Tensor
from ..optimizer import optimizer as opt_mod


def _unwrap(x):
    return x.value if isinstance(x, Tensor) else x


class CompiledTrainStep:
    """Build once per (network, loss, optimizer); call with batches."""

    SUPPORTED = (
        opt_mod.AdamW,  # check subclasses before parents
        opt_mod.Adam,
        opt_mod.Lamb,
        opt_mod.Momentum,
        opt_mod.SGD,
    )

    def __init__(self, network, loss_fn, optimizer, amp_level=None,
                 amp_dtype="bfloat16", scaler=None, layout_policy=None):
        from .dy2static import convert_to_static

        # dy2static pass on the top-level forward so Python if/while on
        # tensor values compile (lax.cond/while_loop) inside the step.
        # The converted forward is swapped in ONLY while tracing the step
        # (_forward_traced) — plain eager calls keep the original method.
        self._converted_forward = None
        fw = network.forward
        if callable(fw) and not hasattr(fw, "_jitted"):
            conv = convert_to_static(fw)
            if getattr(conv, "__func__", conv) is not getattr(
                fw, "__func__", fw
            ):
                self._converted_forward = conv
        self.network = network
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self.amp_dtype = amp_dtype
        # fp16 dynamic loss scaling fused INTO the compiled step: scale
        # the loss, unscale grads, skip the update on inf/nan, and grow/
        # shrink the scale — all in-trace (reference GradScaler + fp16)
        self.scaler = self._normalize_scaler(scaler)
        self._kind = None
        for cls in self.SUPPORTED:
            if type(optimizer) is cls or isinstance(optimizer, cls):
                self._kind = cls
                break
        if self._kind is None:
            raise NotImplementedError(
                f"CompiledTrainStep does not support {type(optimizer).__name__};"
                " use the eager path"
            )
        self._step_fn = None
        self._param_names = [k for k, _ in network.named_parameters()]
        self._checkpoint = None
        self._sentinel = None
        self._watchdog = None
        # sharding layout: an explicit LayoutPolicy (or registry name)
        # pins this trainer; None captures the ACTIVE parallel.layout
        # policy NOW, at construction — so the documented pattern
        # (`with layout.use_policy(...): trainer = ...`, step later)
        # keeps the chosen layout even after the context exits. The
        # policy's optimizer-state / master-param rules are stamped on
        # the step's outputs, so e.g. the pp-sharded-state layout keeps
        # Adam moments sharded over the pp axis across steps (the
        # 29.4 -> 18.4 GiB/chip 7B lever).
        from ..parallel import layout as layout_mod

        self._layout_policy = (
            layout_mod.resolve(layout_policy)
            if layout_policy is not None
            else layout_mod.get_policy()
        )
        # AMP O3 (fp8 matmuls): per-tensor delayed-scaling amax
        # histories, carried through the compiled step next to the
        # optimizer state (structure discovered on the first call)
        self._fp8_state = None
        self._fp8_bytes_saved = 0
        # step-argument avals captured at first invoke — the
        # memory_report() trace input (HBM footprint next to the
        # StepMeter gauges)
        self._step_args_sds = None

    def attach_checkpoint(self, manager):
        """Wire a ``checkpoint.CheckpointManager`` into the step loop:
        after each optimizer step the manager's policy decides whether
        to kick off an async save. The manager is bound to this
        trainer's network/optimizer if it was constructed bare.

        AMP O3: the fp8 delayed-scaling amax histories live outside the
        network/optimizer state dicts, so attaching also registers them
        as manager extra-state — each save persists
        :meth:`fp8_state_dict` in the commit manifest and a restore
        feeds it back through :meth:`load_fp8_state`, making O3
        crash-resumes bit-identical instead of cold-starting scales at
        1. Works in either order with ``restore_or_init()`` (a restore
        that already happened applies at registration)."""
        manager.bind(self.network, self.optimizer)
        self._checkpoint = manager
        if hasattr(manager, "register_extra_state"):
            manager.register_extra_state(
                "fp8", self.fp8_state_dict, self.load_fp8_state
            )
        return manager

    def attach_sentinel(self, sentinel):
        """Wire a ``training.AnomalySentinel`` into the step loop: the
        sentinel sees every step's loss as a lazy device ref and walks
        its skip/rollback/abort policy ladder on NaN/inf or loss
        spikes. The sentinel's checkpoint manager (when it has one) is
        how rollback restores; attach_checkpoint wires saving
        separately."""
        sentinel.bind(self)
        self._sentinel = sentinel
        return sentinel

    def attach_watchdog(self, watchdog):
        """Wire a ``training.TrainWatchdog``: each step's dispatch is
        timestamped (one host clock read) so a wedged step or a
        straggling peer fires before the job dies silently."""
        self._watchdog = watchdog
        return watchdog

    # -------------------------------------------------- sentinel snapshots
    def _memory_snapshot(self):
        """One pre-step on-device snapshot for the sentinel's
        skip-step rung: ``jnp.copy`` per leaf (donation-immune, the
        checkpoint snapshot discipline — no host sync), plus the small
        host-side counters the restore must rewind. The RNG stream is
        deliberately NOT captured: a skipped batch keeps the key
        sequence advancing."""
        snap = {
            "params": {
                k: jnp.copy(p.value)
                for k, p in self.network.named_parameters()
            },
            "buffers": {
                k: jnp.copy(b.value)
                for k, b in self.network.named_buffers()
            },
            "opt_state": {
                k: tuple(jnp.copy(a) for a in accs)
                for k, accs in self._gather_opt_state({}).items()
            },
            "fp8": (
                {k: jnp.copy(v) for k, v in self._fp8_state.items()}
                if self._fp8_state is not None else None
            ),
            "step_count": self.optimizer._step_count,
        }
        if self.scaler is not None:
            sc = self.scaler
            snap["scaler"] = (sc._scale, sc._good_steps, sc._bad_steps)
        return snap

    def _restore_memory_snapshot(self, snap):
        """Undo the step(s) since ``snap`` was taken (skip-step)."""
        lookup = dict(self.network.named_parameters())
        for k, v in snap["params"].items():
            lookup[k].value = v
        self.network.load_functional_state(buffers=snap["buffers"])
        self._scatter_opt_state(snap["opt_state"])
        if snap["fp8"] is not None:
            self._fp8_state = dict(snap["fp8"])
        self.optimizer._step_count = snap["step_count"]
        if self.scaler is not None and "scaler" in snap:
            (self.scaler._scale, self.scaler._good_steps,
             self.scaler._bad_steps) = snap["scaler"]

    def fp8_state_dict(self):
        """The AMP O3 delayed-scaling state as host numpy arrays
        ({site/operand: amax history}), for persisting next to a
        checkpoint. Empty dict when O3 is off or not yet discovered."""
        import numpy as _np

        if self._fp8_state is None:
            return {}
        return {k: _np.asarray(v) for k, v in self._fp8_state.items()}

    def load_fp8_state(self, state):
        """Restore delayed-scaling histories saved by
        :meth:`fp8_state_dict` (keys must match the model's matmul
        sites — same architecture, same call order)."""
        if not state:
            return
        self._fp8_state = {
            k: jnp.asarray(v, jnp.float32) for k, v in state.items()
        }

    @staticmethod
    def _normalize_scaler(scaler):
        """A disabled GradScaler is the same as no scaler (shared with
        callers that need to compare against self.scaler)."""
        if scaler is not None and getattr(scaler, "_enable", True):
            return scaler
        return None

    # ------------------------------------------------------------ opt state
    def _gather_opt_state(self, params):
        opt = self.optimizer
        state = {}
        if self._kind in (opt_mod.Adam, opt_mod.AdamW, opt_mod.Lamb):
            for k, p in self.network.named_parameters():
                state[k] = (
                    opt._acc(p, "moment1"),
                    opt._acc(p, "moment2"),
                )
        elif self._kind is opt_mod.Momentum:
            for k, p in self.network.named_parameters():
                state[k] = (opt._acc(p, "velocity"),)
        else:  # SGD
            for k in self._param_names:
                state[k] = ()
        return state

    def _scatter_opt_state(self, state):
        opt = self.optimizer
        names = {k: p for k, p in self.network.named_parameters()}
        for k, accs in state.items():
            p = names[k]
            if self._kind in (opt_mod.Adam, opt_mod.AdamW, opt_mod.Lamb):
                opt._set_acc(p, "moment1", accs[0])
                opt._set_acc(p, "moment2", accs[1])
            elif self._kind is opt_mod.Momentum:
                opt._set_acc(p, "velocity", accs[0])

    def _forward_traced(self, inputs):
        """Network invocation inside the traced step (hook: the pipeline
        trainer overrides this to run the stacked-stage shard_map
        schedule instead of the sequential forward)."""
        if self._converted_forward is None:
            return self.network(*(Tensor(v) for v in inputs))
        # temporary swap so Layer.__call__ hooks still run around the
        # dy2static-converted body; restored even if tracing throws
        d = self.network.__dict__
        had_own = "forward" in d
        prev = d.get("forward")
        d["forward"] = self._converted_forward
        try:
            return self.network(*(Tensor(v) for v in inputs))
        finally:
            if had_own:
                d["forward"] = prev
            else:
                d.pop("forward", None)

    # ----------------------------------------------------------- pure step
    def _build(self):
        network = self.network
        loss_fn = self.loss_fn
        opt = self.optimizer
        kind = self._kind
        amp_level = self.amp_level
        amp_dtype = self.amp_dtype

        clip = opt._grad_clip
        from ..optimizer.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue

        # id -> structured name, built once (7B-scale param trees: O(n))
        name_of = {id(p): k for k, p in network.named_parameters()}
        wd_coeffs, lr_mults = {}, {}
        decay_fun = getattr(opt, "_apply_decay_fun", None)
        for group, p in opt._all_params():
            name = name_of[id(p)]
            if decay_fun is not None and not decay_fun(p.name or ""):
                # eager AdamW parity: the exclusion only suppresses the
                # optimizer-level weight_decay; a per-param regularizer or
                # group-level weight_decay still applies
                wd_backup = opt._weight_decay
                opt._weight_decay = 0.0
                try:
                    coeff, l1 = opt._decay_value(group, p)
                finally:
                    opt._weight_decay = wd_backup
            else:
                coeff, l1 = opt._decay_value(group, p)
            if l1 == "l1":
                raise NotImplementedError(
                    "CompiledTrainStep does not support L1Decay "
                    f"(parameter {name!r}); use the eager optimizer path"
                )
            wd_coeffs[name] = float(coeff)
            lr_mults[name] = float(
                group.get("learning_rate", 1.0)
            ) * float(p.optimize_attr.get("learning_rate", 1.0))

        hyper = {}
        if kind in (opt_mod.Adam, opt_mod.AdamW, opt_mod.Lamb):
            hyper = dict(beta1=opt._beta1, beta2=opt._beta2, eps=opt._eps)
        elif kind is opt_mod.Momentum:
            hyper = dict(mu=opt._momentum, nesterov=opt._nesterov)

        def loss_of(params, buffers, rng, inputs, labels,
                    fp8_state=None):
            network.load_functional_state(params, buffers)
            if amp_level in ("O1", "O2", "O3"):
                from ..amp import auto_cast

                # O3 keeps O1's bf16/fp32 op split for everything that
                # is NOT a matmul; the matmuls themselves are routed to
                # fp8 by the context below
                cm = auto_cast(
                    True, level="O1" if amp_level == "O3" else amp_level,
                    dtype=amp_dtype,
                )
            else:
                import contextlib

                cm = contextlib.nullcontext()
            if amp_level == "O3":
                from ..amp import fp8 as fp8_mod

                fp8_cm = fp8_mod.fp8_autocast(fp8_state)
            else:
                import contextlib

                fp8_cm = contextlib.nullcontext()
            with tape.trace_scope(), tape.no_grad(), \
                    random_mod.key_scope(rng), cm, fp8_cm as fp8_ctx:
                network.train()
                out = self._forward_traced(inputs)
                outs = out if isinstance(out, (list, tuple)) else [out]
                with jax.named_scope("loss"):
                    loss = loss_fn(
                        *(list(outs) + [Tensor(v) for v in labels])
                    )
            new_buffers = {k: b.value for k, b in network.named_buffers()}
            out_vals = tuple(o.value for o in outs)
            if fp8_ctx is not None:
                # delayed-scaling histories ride the step like buffers:
                # in as carried state, out updated with this step's
                # amaxes (device arrays end to end — no host sync)
                self._fp8_bytes_saved = fp8_ctx.weight_bytes_saved
                new_fp8 = fp8_ctx.new_state
            else:
                new_fp8 = None
            return loss.value.astype(jnp.float32), (
                new_buffers, out_vals, new_fp8,
            )

        self._loss_of = loss_of

        # ZeRO stage-2/3 (group_sharded): constrain grads to the sharded
        # layout; XLA realizes the reduce-scatter + sharded-update pattern
        grad_placements = getattr(opt, "_grad_placements", None) or {}

        # layout-policy memory levers: stamp the policy's optimizer-state
        # (and master-param) shardings on the step outputs so the lowered
        # module carries them and the write-back keeps them steady-state.
        # The default tp-pp-dp policy produces NO pins — the step stays
        # byte-identical to the pre-policy trainer.
        from ..parallel import mesh as mesh_mod

        pol = self._layout_policy
        policy_state_pins, policy_param_pins = {}, {}
        if mesh_mod.mesh_defined() and (
            pol.pp_shard_optimizer_state or pol.pp_shard_master_params
        ):
            for k, p in network.named_parameters():
                sh = pol.optimizer_state_sharding(p.value)
                if sh is not None:
                    policy_state_pins[k] = sh
                sh = pol.master_param_sharding(p.value)
                if sh is not None:
                    policy_param_pins[k] = sh

        scaler = self.scaler

        def apply_update(params, opt_state, grads, lr, t):
            """Gradient clipping and the optimizer's update of every
            parameter: the part of the step under scope ``optimizer``."""
            # gradient clipping (global-norm path fused into the step)
            if isinstance(clip, ClipGradByGlobalNorm):
                sq = sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)
                )
                gnorm = jnp.sqrt(sq)
                # NOT named `scale`: that closure variable is the fp16
                # loss scale, which the scaler update below reads
                clip_coef = jnp.minimum(
                    1.0, clip.clip_norm / jnp.maximum(gnorm, 1e-12)
                )
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) * clip_coef).astype(
                        g.dtype
                    ),
                    grads,
                )
            elif isinstance(clip, ClipGradByNorm):
                def _pn(g):
                    n = jnp.sqrt(jnp.sum(jnp.square(g)))
                    s = jnp.where(n > clip.clip_norm, clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
                    return g * s

                grads = jax.tree_util.tree_map(_pn, grads)
            elif isinstance(clip, ClipGradByValue):
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, clip.min, clip.max), grads
                )

            new_params, new_state = {}, {}
            for k in params:
                p, g = params[k], grads[k]
                wd = wd_coeffs.get(k, 0.0)
                plr = lr * lr_mults.get(k, 1.0)
                if kind is opt_mod.SGD:
                    if wd:
                        g = g + wd * p
                    new_params[k] = opt_mod._sgd_update.__wrapped__(p, g, plr)
                    new_state[k] = ()
                elif kind is opt_mod.Momentum:
                    if wd:
                        g = g + wd * p
                    (vel,) = opt_state[k]
                    np_, v2 = opt_mod._momentum_update.__wrapped__(
                        p, vel, g, plr, hyper["mu"], hyper["nesterov"]
                    )
                    new_params[k] = np_
                    new_state[k] = (v2,)
                elif kind in (opt_mod.Adam, opt_mod.AdamW):
                    m, v = opt_state[k]
                    decoupled = kind is opt_mod.AdamW
                    np_, m2, v2 = opt_mod._adam_update.__wrapped__(
                        p, m, v, g, plr, hyper["beta1"], hyper["beta2"],
                        hyper["eps"], t, wd, decoupled,
                    )
                    new_params[k] = np_
                    new_state[k] = (m2, v2)
                else:  # Lamb
                    m, v = opt_state[k]
                    np_, m2, v2 = opt_mod._lamb_update.__wrapped__(
                        p, m, v, g, plr, hyper["beta1"], hyper["beta2"],
                        hyper["eps"], t, opt._lamb_wd,
                    )
                    new_params[k] = np_
                    new_state[k] = (m2, v2)
            return new_params, new_state

        def step(params, opt_state, buffers, lr, t, rng, inputs, labels,
                 scale=None, good=None, bad=None, fp8_state=None):
            if scaler is not None:
                def scaled_loss_of(params, buffers, rng, inputs, labels):
                    loss, aux = loss_of(params, buffers, rng, inputs,
                                        labels, fp8_state=fp8_state)
                    return loss * scale, (aux, loss)

                (
                    (_, ((new_buffers, out_vals, new_fp8), loss)),
                    grads,
                ) = jax.value_and_grad(scaled_loss_of, has_aux=True)(
                    params, buffers, rng, inputs, labels
                )
                inv = (1.0 / scale).astype(jnp.float32)
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype),
                    grads,
                )
                finite = jnp.all(jnp.asarray([
                    jnp.all(jnp.isfinite(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)
                ]))
            else:
                (loss, (new_buffers, out_vals, new_fp8)), grads = \
                    jax.value_and_grad(loss_of, has_aux=True)(
                        params, buffers, rng, inputs, labels,
                        fp8_state,
                    )
                finite = None

            if grad_placements:
                grads = {
                    k: (
                        jax.lax.with_sharding_constraint(
                            g, grad_placements[k]
                        )
                        if k in grad_placements
                        else g
                    )
                    for k, g in grads.items()
                }

            with jax.named_scope("optimizer"):
                new_params, new_state = apply_update(
                    params, opt_state, grads, lr, t
                )

            if policy_state_pins or policy_param_pins:
                new_state = {
                    k: tuple(
                        (
                            jax.lax.with_sharding_constraint(
                                a, policy_state_pins[k]
                            )
                            if k in policy_state_pins and a.ndim
                            else a
                        )
                        for a in accs
                    )
                    for k, accs in new_state.items()
                }
                new_params = {
                    k: (
                        jax.lax.with_sharding_constraint(
                            v, policy_param_pins[k]
                        )
                        if k in policy_param_pins
                        else v
                    )
                    for k, v in new_params.items()
                }

            if scaler is not None:
                # non-finite grads: keep params/state, adjust the scale
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda a, b: jnp.where(finite, a, b), new, old
                )
                new_params = keep(new_params, params)
                new_state = keep(new_state, opt_state)
                good2 = jnp.where(finite, good + 1, 0)
                bad2 = jnp.where(finite, 0, bad + 1)
                if scaler._dynamic:
                    scale2 = jnp.where(
                        good2 >= scaler._incr_every,
                        scale * scaler._incr_ratio, scale,
                    )
                    good2 = jnp.where(
                        good2 >= scaler._incr_every, 0, good2
                    )
                    # decrease floors at 1.0 (eager update() parity):
                    # an unfloored scale decays to 0 and 1/scale poisons
                    # every later step
                    scale2 = jnp.where(
                        bad2 >= scaler._decr_every,
                        jnp.maximum(scale * scaler._decr_ratio, 1.0),
                        scale2,
                    )
                    bad2 = jnp.where(bad2 >= scaler._decr_every, 0, bad2)
                else:
                    scale2 = scale  # static-scale mode: never adjusted
                return (new_params, new_state, new_buffers, loss, out_vals,
                        new_fp8, scale2, good2, bad2, finite)
            return (new_params, new_state, new_buffers, loss, out_vals,
                    new_fp8)

        self._step = step

    @staticmethod
    def _explicit_sharding(x):
        """A sharding worth pinning: an explicit NamedSharding on a
        multi-device mesh (ZeRO/FSDP placement invariants). Plain
        single-device placements must NOT be pinned — pinning them
        disables XLA's layout freedom and donation fast path (measured
        70x single-chip slowdown in round 2) and breaks runs whose
        inputs later live on a mesh."""
        s = getattr(x, "sharding", None)
        if isinstance(s, jax.sharding.NamedSharding) and s.mesh.size > 1:
            return s
        return None

    def _finalize_jit(self, params, opt_state, buffers):
        """Keep sharded optimizer state / FSDP params sharded across
        steps (ZeRO stages are placement invariants, not one-shot
        placements) by constraining ONLY the leaves that arrived with an
        explicit multi-device NamedSharding. Everything else is left to
        XLA's sharding propagation + donation, which preserves
        placements on the common path without the cost of output
        pinning."""
        param_pins = {
            k: self._explicit_sharding(v) for k, v in params.items()
        }
        state_pins = {
            k: tuple(self._explicit_sharding(a) for a in accs)
            for k, accs in opt_state.items()
        }
        buffer_pins = {
            k: self._explicit_sharding(v) for k, v in buffers.items()
        }
        base = self._step
        any_pin = (
            any(param_pins.values())
            or any(buffer_pins.values())
            or any(s for pins in state_pins.values() for s in pins)
        )
        if any_pin:
            def step(params, opt_state, buffers, lr, t, rng, inputs, labels,
                     *extra):
                new_params, new_state, new_buffers, loss, out_vals, *rest = \
                    base(params, opt_state, buffers, lr, t, rng, inputs,
                         labels, *extra)
                new_params = {
                    k: (
                        jax.lax.with_sharding_constraint(v, param_pins[k])
                        if param_pins.get(k) is not None
                        else v
                    )
                    for k, v in new_params.items()
                }
                new_state = {
                    k: tuple(
                        (
                            jax.lax.with_sharding_constraint(a, pin)
                            if pin is not None
                            else a
                        )
                        for a, pin in zip(accs, state_pins[k])
                    )
                    for k, accs in new_state.items()
                }
                new_buffers = {
                    k: (
                        jax.lax.with_sharding_constraint(v, buffer_pins[k])
                        if buffer_pins.get(k) is not None
                        else v
                    )
                    for k, v in new_buffers.items()
                }
                return (new_params, new_state, new_buffers, loss, out_vals,
                        *rest)
        else:
            step = base
        self._step_fn = jax.jit(step, donate_argnums=(0, 1, 2))

    def _invoke(self, *step_args):
        """Run the jitted step, translating XLA's unbounded-while reverse-AD
        limitation into an actionable paddle-level error."""
        if self._step_args_sds is None:
            # avals only — donation below frees the buffers; the
            # shapes/dtypes and the mesh placements stay valid for
            # memory_report()'s re-trace and for lowering the step again
            self._step_args_sds = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    jnp.shape(a), jnp.result_type(a),
                    sharding=self._explicit_sharding(a),
                ),
                step_args,
            )
        try:
            return self._step_fn(*step_args)
        except ValueError as e:
            msg = str(e)
            if "Reverse-mode differentiation" in msg and "while_loop" in msg:
                from .dy2static import Dy2StaticError

                raise Dy2StaticError(
                    "a value-dependent `while` loop inside the training "
                    "step is not reverse-differentiable on XLA. If the "
                    "loop result needs gradients, bound the loop: "
                    "paddle.static.nn.while_loop(..., maximum_trip_count="
                    "N) lowers to a fixed-length masked scan that trains; "
                    "or rewrite with a concrete Python trip count "
                    "(unrolled). Unbounded tensor-condition loops are "
                    "inference-only."
                ) from e
            raise

    def memory_report(self):
        """Donation-aware live-range HBM estimate of the compiled step
        (``analysis.memory_lint``): peak resident bytes with params/
        opt-state/buffers donated, next to the StepMeter's timing
        gauges. Re-traces the step body at the captured argument avals
        (no FLOPs, no compile); None before the first step. The trace
        swaps tracers through the imperative layers, so the network's
        concrete state is restored before returning."""
        if self._step_fn is None or self._step_args_sds is None:
            return None
        from .. import analysis
        from ..parallel import layout as layout_mod

        params = {k: p.value for k, p in self.network.named_parameters()}
        buffers = {k: b.value for k, b in self.network.named_buffers()}
        try:
            with layout_mod.use_policy(self._layout_policy):
                est = analysis.estimate_fn(
                    self._step_fn, *self._step_args_sds,
                    graph="train_step", donate_argnums=(0, 1, 2),
                )
        finally:
            self.network.load_functional_state(params, buffers)
        return est.to_dict()

    def _publish_memory_gauge(self):
        """Opt-in (``PADDLE_TPU_TRAIN_MEMORY_GAUGE=1``): publish the
        train step's estimated peak as a gauge on the first real step.
        Off by default — the re-trace costs one extra trace of the
        step body at warmup."""
        import os

        if not os.environ.get("PADDLE_TPU_TRAIN_MEMORY_GAUGE"):
            return
        rep = self.memory_report()
        if rep is None:
            return
        from .. import observability as obs

        g = obs.get_registry().gauge(
            "paddle_train_step_peak_bytes",
            help="estimated peak resident bytes of the compiled train "
                 "step (memory_lint live-range model, donation-aware)",
            unit="bytes",
        )
        g.set(float(rep["peak_bytes"]))

    def _record_telemetry(self, dt, in_vals, loss, warmup):
        """Publish one step into the process StepMeter (observability).

        Host-side only: batch geometry comes from input SHAPES and the
        loss is handed over as a device ref the meter's lazy gauge
        fetches on scrape — no sync is added to the step. The first
        call per program is reported as ``warmup`` (its wall time is
        dominated by trace+XLA compile and goes to the compile_time
        histogram, not step_time). Telemetry can never fail a train
        step."""
        try:
            from .. import observability as obs

            meter = obs.get_step_meter()
            meter.auto_configure(self.network)  # MFU from model config
            examples, tokens = obs.batch_geometry(in_vals)
            meter.observe_step(
                dt, examples=examples, tokens=tokens, loss=loss,
                warmup=warmup,
            )
            if self.amp_level == "O3" and self._fp8_bytes_saved:
                # analytic per-step HBM delta of routing the matmul
                # weights through fp8 (counted at trace time)
                meter.note_fp8_bytes_saved(self._fp8_bytes_saved)
            if warmup:
                self._publish_memory_gauge()
        except Exception:
            pass

    # ---------------------------------------------------------------- call
    def __call__(self, inputs, labels):
        """One optimizer step. The trainer's captured layout policy is
        ACTIVE for the whole call: policy-routed code that resolves the
        policy at trace time (ParallelCrossEntropy / causal_lm_loss,
        sep-ring attention, Optimizer._acc accumulator births) sees the
        trainer's layout even when the step runs outside the
        use_policy context the trainer was constructed in — otherwise
        the layout would apply half-way (pinned state, default loss)."""
        from ..parallel import layout as layout_mod

        with layout_mod.use_policy(self._layout_policy):
            return self._step_once(inputs, labels)

    def _step_once(self, inputs, labels):
        _t0 = time.perf_counter()
        _warmup = self._step_fn is None  # first call traces + compiles
        if self._step_fn is None:
            self._build()
        params = {k: p.value for k, p in self.network.named_parameters()}
        for k, v in params.items():
            if isinstance(v, jax.ShapeDtypeStruct):
                raise RuntimeError(
                    f"parameter {k!r} is still abstract (built under "
                    "paddle.LazyGuard): call network.materialize() or "
                    "load a checkpoint before training. Abstract "
                    "networks can only be lowered (jit(...).lower), "
                    "not executed."
                )
        step_next = self.optimizer._step_count + 1
        if self._sentinel is not None:
            # pre-step snapshot for the skip rung — BEFORE the gather
            # below hands these arrays to the donating jit
            self._sentinel.before_step(step_next)
        if self._watchdog is not None:
            self._watchdog.note_dispatch(step_next)
        # chaos seams: a blocking callback here is the deterministic
        # wedged step, an os._exit callback the deterministic dead rank
        _chaos.poke("train.step_begin", step=step_next)
        buffers = {k: b.value for k, b in self.network.named_buffers()}
        opt_state = self._gather_opt_state(params)
        if self._step_fn is None:  # (compile happens on first _invoke)
            self._finalize_jit(params, opt_state, buffers)
        self.optimizer._step_count += 1
        lr = jnp.float32(self.optimizer.get_lr())
        t = jnp.float32(self.optimizer._step_count)
        rng = random_mod.next_key()
        in_vals = tuple(_unwrap(x) for x in inputs)
        lbl_vals = tuple(_unwrap(y) for y in labels)
        if self.amp_level == "O3" and self._fp8_state is None:
            # discover the fp8 delayed-scaling state STRUCTURE with an
            # abstract pass (jax.eval_shape — no compile, no FLOPs), so
            # the compiled step's signature includes the carried
            # histories from its one and only trace
            shapes = jax.eval_shape(
                lambda p, b, r, i, l: self._loss_of(
                    p, b, r, i, l, None
                )[1][2],
                params, buffers, rng, in_vals, lbl_vals,
            )
            self._fp8_state = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes
            )
            # eval_shape left abstract tracers in the Layer objects
            self.network.load_functional_state(params, buffers)
        if self.scaler is not None:
            sc = self.scaler
            (new_params, new_state, new_buffers, loss, out_vals,
             new_fp8, scale2, good2, bad2, finite) = self._invoke(
                params, opt_state, buffers, lr, t, rng, in_vals, lbl_vals,
                jnp.float32(sc._scale), jnp.int32(sc._good_steps),
                jnp.int32(sc._bad_steps), self._fp8_state,
            )
            sc._scale = float(scale2)
            sc._good_steps = int(good2)
            sc._bad_steps = int(bad2)
            sc._found_inf = not bool(finite)
            if sc._found_inf:
                # the update was skipped: bias-correction time must not
                # advance (reference optimizers see no step either)
                self.optimizer._step_count -= 1
        else:
            (new_params, new_state, new_buffers, loss, out_vals,
             new_fp8) = self._invoke(
                params, opt_state, buffers, lr, t, rng, in_vals,
                lbl_vals, None, None, None, self._fp8_state,
            )
        if new_fp8 is not None:
            # device arrays in, device arrays out — the histories never
            # touch the host (the step stays sync-free)
            self._fp8_state = new_fp8
        # chaos value seam: a callback returning float("nan") is the
        # deterministic anomaly the sentinel ladder must recover from
        injected = _chaos.poke_value(
            "train.loss", loss, step=self.optimizer._step_count
        )
        if injected is not loss:
            loss = jnp.asarray(injected, jnp.float32)
        # write back: imperative objects stay the source of truth
        lookup = dict(self.network.named_parameters())
        for k, v in new_params.items():
            lookup[k].value = v
        self.network.load_functional_state(buffers=new_buffers)
        self._scatter_opt_state(new_state)
        self._record_telemetry(time.perf_counter() - _t0, in_vals, loss,
                               _warmup)
        action = None
        if self._sentinel is not None:
            # may raise RollbackAndReplay (state already restored to
            # the last commit) or TrainingAborted (bundle dumped);
            # returns the Action when the ladder chose skip-step
            action = self._sentinel.after_step(
                self.optimizer._step_count, loss
            )
        if self._checkpoint is not None and action is None:
            # after write-back AND the sentinel verdict: a step the
            # sentinel just undid must not be checkpointed. Policy
            # check + on-device snapshot only — the write happens on
            # the manager's background thread
            self._checkpoint.on_step(self.optimizer._step_count)
        return Tensor(loss), [Tensor(o) for o in out_vals]
