"""The fused hybrid-parallel train step.

Replaces the reference's entire per-step runtime — eager op dispatch +
GradNode backward walk + DP reducer hooks + sharding-optimizer
reduce-scatter + TP identity/allreduce ops + LR-scheduler python — with ONE
jitted program (reference call stack: SURVEY.md §3.4). XLA sees forward,
backward, grad clip and the optimizer update together, so it fuses the
update into the backward epilogue and schedules every collective (grad
reduce-scatter over 'dp'/'fsdp', activation collectives over 'mp'/'sp')
against compute over ICI — what the reference approximates with comm
streams and hooks.

Memory notes: params+opt state are donated (buffers reused in place);
compute runs in bf16 with fp32 params (AMP-O2 master-weights contract,
reference: hybrid_parallel_optimizer.py + GradScaler) — on TPU there is no
loss scaling because bf16 has fp32's exponent range.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu import observability
from paddle_tpu.core import compile_cache, jax_compat
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.functional import functional_call, state_tensors
from paddle_tpu.parallel.plan import ShardingPlan, batch_spec


@dataclass
class TrainStepConfig:
    compute_dtype: Any = "bfloat16"   # forward/backward dtype; None = as-is
    grad_accum_steps: int = 1         # microbatch loop via lax.scan
    donate: bool = True
    shard_batch_seq: bool = True      # shard (B, S) seq dim over 'sp'
    context_parallel: str | None = None  # 'ring' | 'ulysses' over 'sp'
    # params whose grad gets an optimization_barrier before the optimizer
    # update. XLA fuses the Adam update (3 f32 reads + 3 f32 writes of
    # the weight) into the dW matmul epilogue; for vocab-sized weights
    # that interleaving measured the lm_head dW at 46% MXU eff on v5e —
    # the barrier splits matmul and update (+3% step throughput). A
    # global barrier is WORSE (materializes every grad); name-match only
    # the big vocab params (substrings of names; '1' = all).
    opt_barrier_params: tuple = ("lm_head", "embed_tokens")
    # keep Adam moments in PINNED HOST memory between steps (reference:
    # sharding/group_sharded_optimizer_stage2.py offload=True + the
    # pinned allocator, allocator_facade host-pinned pool): frees
    # 8 bytes/param of HBM for activations/batch at the cost of a
    # host<->HBM round trip per step. TPU-native via jax memory kinds.
    offload_opt_state: bool = False
    # non-finite-gradient skip (reference: the check_nan_inf + GradScaler
    # found-inf skip the reference applies under fp16): when any grad
    # (or the loss) is Inf/NaN the whole update is suppressed in-jit —
    # params and optimizer state pass through unchanged — and the step
    # reports skipped=True. Opt-in: enabling adds an isfinite reduction
    # + per-param selects to the compiled step, so the default keeps the
    # hot path byte-identical.
    skip_nonfinite_grads: bool = False
    # consecutive skipped steps before the trainer ABORTS (a diverged
    # run burning pod-hours silently is worse than a crash; bounded like
    # the reference's FLAGS_check_nan_inf hard stop)
    max_consecutive_nonfinite: int = 25
    # how many steps of skip flags to buffer before the host reads them
    # (each read syncs on that step; 1 = check every step, larger keeps
    # more dispatch pipelining and still aborts within the window)
    nonfinite_check_every: int = 1
    # training-sentry health probe (distributed/sentry.py): the compiled
    # step additionally returns probe = [global_grad_norm, applied] and
    # takes a loss-cap scalar input; an update whose loss/grads are
    # non-finite OR whose loss exceeds the cap is suppressed in-jit
    # (same select-don't-branch machinery as skip_nonfinite_grads, which
    # this subsumes — the two knobs are mutually exclusive). The probe
    # rides the step's existing outputs: no extra host sync is added
    # here; reading it is the sentry's decision.
    health_probe: bool = False
    # decomposed FSDP collectives (ISSUE 19; parallel/overlap.py): the
    # loss closure runs under overlap_fsdp_guard so the model's
    # FSDP-critical projections stream their weight all-gather around a
    # chunked ppermute ring UNDER the matmul instead of ahead of it.
    # overlap_chunks = sub-chunks per resident shard (finer
    # pipelining). No-op when the mesh lacks an 'fsdp' axis; off by
    # default so the hot path stays byte-identical.
    overlap_fsdp: bool = False
    overlap_chunks: int = 1


class NonFiniteGradError(RuntimeError):
    """max_consecutive_nonfinite steps in a row produced Inf/NaN grads —
    the run has diverged; aborting beats silently skipping forever."""


def _cast_tree(tree, dtype):
    if dtype is None:
        return tree
    dt = jnp.dtype(dtype)
    return jax.tree.map(
        lambda a: a.astype(dt)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _strip_axis(spec: P, axis: str) -> P:
    """`spec` with `axis` removed from every entry (tuple entries
    keep their other axes) — the nocomm phase-timing twin replicates
    params over 'fsdp' with this."""
    out = []
    for entry in spec:
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a != axis)
            out.append(kept if kept else None)
        else:
            out.append(None if entry == axis else entry)
    return P(*out)


def _memories_supported() -> bool:
    """pinned_host placement works on TPU; the CPU emulation backend
    has the memory SPACES but no lowering for the placement
    custom-call."""
    if not jax_compat.on_tpu():
        return False
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    return "pinned_host" in kinds


def _opt_barrier(grads: dict, cfg) -> dict:
    """optimization_barrier on grads of cfg.opt_barrier_params-matching
    names (see TrainStepConfig.opt_barrier_params for the why)."""
    pats = list(getattr(cfg, "opt_barrier_params", ()) or ())
    if not pats:
        return grads
    return {n: (jax.lax.optimization_barrier(g)
                if "1" in pats or any(p in n for p in pats)
                else g)
            for n, g in grads.items()}


class Trainer:
    """Functional training state + compiled step for (model, optimizer) on
    a mesh. The eager Layer/Optimizer objects remain the API surface
    (state_dict, checkpointing); this class owns the performance path."""

    def __init__(self, model, optimizer, mesh: Mesh | None = None,
                 plan: ShardingPlan | None = None,
                 config: TrainStepConfig | None = None,
                 loss_fn: Callable | None = None,
                 checkpointer=None):
        from paddle_tpu.distributed.mesh import ProcessMesh
        compile_cache.ensure()
        if isinstance(mesh, ProcessMesh):
            mesh = mesh.jax_mesh
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.plan = plan
        # optional distributed.async_checkpoint.AsyncCheckpointer:
        # save_checkpoint() then returns after only the device->host
        # snapshot and the write overlaps subsequent steps
        self.checkpointer = checkpointer
        import dataclasses
        # private copy: the trainer mutates offload_opt_state (model
        # hint / backend fallback) and must not write into a config
        # object the caller may share across trainers
        self.config = dataclasses.replace(config) if config is not None \
            else TrainStepConfig()
        if getattr(model, "_sharding_offload", False):
            # group_sharded_parallel(offload=True) hint
            self.config.offload_opt_state = True
        if self.config.health_probe and self.config.skip_nonfinite_grads:
            raise ValueError(
                "TrainStepConfig.health_probe subsumes "
                "skip_nonfinite_grads (the probe's in-jit suppression "
                "covers non-finite updates); enable only one")
        self._loss_fn = loss_fn
        self._step_fn = None
        self._chaos_poison = False
        # extra compiled-step inputs, in positional order (subset of
        # ("poison", "spike", "loss_cap")), decided at trace time
        self._extra_names: tuple = ()
        self._poison_sites: tuple = ()
        # sentry loss cap: an update with loss above this is suppressed
        # in-jit when health_probe is on (+inf = never; the sentry
        # quantizes its cap so the staged scalar rarely re-transfers)
        self._loss_cap = float("inf")
        self._cap_cache = None
        # transient LR scale (sentry post-rollback dampening ramp)
        self._lr_scale = 1.0
        # the lazy probe array of the most recent step (health_probe):
        # [global_grad_norm, applied]; reading it is the caller's sync
        self.last_probe = None
        # per-(key, ndim) NamedSharding cache for batch leaves: shared
        # by step() and data_iter()'s prefetcher, so a prefetched batch
        # compares equal (same objects) and skips device_put entirely
        self._batch_shardings: dict = {}
        # non-finite skip bookkeeping (host side)
        self._pending_skips: list = []
        self.nonfinite_streak = 0
        self.nonfinite_skipped = 0
        # step telemetry (observability.telemetry.TrainingTelemetry),
        # built lazily on the first step with observability enabled
        self._telemetry = None
        self._tel_last_t = None
        self._tel_prev = None
        self._init_state()

    # -- state -------------------------------------------------------------
    def _init_state(self):
        tensors = state_tensors(self.model)
        self.param_names = [n for n, t in tensors.items()
                            if not t.stop_gradient]
        self.params = {n: t._value for n, t in tensors.items()}
        self.opt_state = self.optimizer.init_state_arrays(
            {n: self.params[n] for n in self.param_names})
        if self.mesh is not None and self.plan is not None:
            self._shard_state()
        if self.config.offload_opt_state:
            if _memories_supported():
                self._offload_opt_state()
            else:
                import warnings
                warnings.warn(
                    "offload_opt_state: this backend has no pinned_host "
                    "memory space (CPU emulation lacks the placement "
                    "op); keeping optimizer state in device memory")
                self.config.offload_opt_state = False

    def _spec(self, name):
        return self.plan.spec_for(name)

    def _opt_leaf_sharding(self, name, v, kind=None):
        """Sharding for one optimizer-state leaf: moments shard like
        their parameter, scalars replicate; `kind` selects the memory
        space ('pinned_host' while parked between steps under
        offload_opt_state, 'device' inside the step)."""
        if self.mesh is not None:
            spec = (self._spec(name)
                    if getattr(v, "ndim", 0) == len(self.params[name].shape)
                    else P())
            return NamedSharding(self.mesh, spec, memory_kind=kind)
        from jax.sharding import SingleDeviceSharding
        return SingleDeviceSharding(jax.devices()[0], memory_kind=kind)

    def _offload_opt_state(self):
        """Park moments in pinned host memory (reference:
        group_sharded_optimizer_stage2.py offload=True; the pinned pool
        of allocator_facade) — HBM holds them only during the update."""
        self.opt_state = {
            n: {k: jax.device_put(
                v, self._opt_leaf_sharding(n, v, "pinned_host"))
                for k, v in st.items()}
            for n, st in self.opt_state.items()}

    @staticmethod
    def _put_global(v, sh):
        """device_put that tolerates COMMITTED local arrays when the
        target sharding spans non-addressable devices (multi-process
        resume: checkpoint loads commit values to local devices; jax
        only re-spreads uncommitted/host values across processes)."""
        try:
            return jax.device_put(v, sh)
        except ValueError:
            import numpy as np
            return jax.device_put(np.asarray(v), sh)

    def _shard_state(self):
        tensors = state_tensors(self.model)
        for n in list(self.params):
            sh = NamedSharding(self.mesh, self._spec(n))
            self.params[n] = self._put_global(self.params[n], sh)
            # the Layer tree follows: left pointing at the array it was
            # built with, it keeps a whole unsharded copy of the weights
            # alive on the default device (4.4 GB of chip 0 for a 1.1B
            # model) beside the sharded one the step uses. (A subclass's
            # derived entries — the pipeline's stacked layers — have no
            # tensor of their own.)
            if n in tensors:
                tensors[n]._value = self.params[n]
        # optimizer moments shard exactly like their parameter; scalars
        # (beta_pow) replicate. This is ZeRO sharding of optimizer state
        # (reference: dygraph_sharding_optimizer.py:48) for free.
        for n, st in self.opt_state.items():
            for k, v in st.items():
                st[k] = self._put_global(v,
                                         self._opt_leaf_sharding(n, v))

    # -- the compiled step -------------------------------------------------
    def _loss_from_batch(self, params_c, batch):
        """batch: dict of arrays -> scalar loss (f32)."""
        targs = {k: Tensor(v, stop_gradient=True) for k, v in batch.items()}
        if self._loss_fn is not None:
            out = self._loss_fn(self.model, params_c, targs)
        else:
            out = functional_call(self.model, params_c, **targs)
        loss = out[0] if isinstance(out, (tuple, list)) else out
        arr = loss._value if isinstance(loss, Tensor) else loss
        return arr.astype(jnp.float32)

    def _make_loss_for(self, overlap: bool | None = None):
        """The step's loss closure (cast + batch sharding constraint +
        context-parallel / FSDP-overlap guards) — shared by
        `_build_step` and the phase-attributed timing twins in
        `measure_phase_seconds`, so phase timings measure the SAME
        program the fused step runs. `overlap` overrides
        cfg.overlap_fsdp (the timing twins force it off to measure the
        propagated baseline against the same weights)."""
        cfg = self.config
        mesh = self.mesh
        if overlap is None:
            overlap = cfg.overlap_fsdp
        overlap = bool(overlap and mesh is not None
                       and "fsdp" in mesh.axis_names)

        def loss_for(params, batch):
            params_c = _cast_tree(params, cfg.compute_dtype)
            if mesh is not None and cfg.shard_batch_seq:
                bspec = batch_spec(mesh.axis_names)
                batch = {
                    k: jax.lax.with_sharding_constraint(
                        v, NamedSharding(mesh, P(*(
                            list(bspec) + [None] * (v.ndim - 2))[:v.ndim])))
                    if v.ndim >= 1 else v
                    for k, v in batch.items()}
            with contextlib.ExitStack() as stack:
                if cfg.context_parallel and mesh is not None:
                    from paddle_tpu.distributed.context_parallel import (
                        context_parallel_guard)
                    stack.enter_context(context_parallel_guard(
                        mesh, axis="sp", mode=cfg.context_parallel))
                if overlap:
                    from paddle_tpu.parallel.overlap import (
                        overlap_fsdp_guard)
                    stack.enter_context(overlap_fsdp_guard(
                        mesh, axis="fsdp",
                        chunks=max(1, cfg.overlap_chunks)))
                return self._loss_from_batch(params_c, batch)

        return loss_for

    def _build_step(self, batch_treedef):
        cfg = self.config
        # chaos injection is gated at TRACE time: with chaos off the
        # compiled step has no poison/spike inputs at all — the hot
        # path stays byte-identical. "trainer.grad"/"train.grad.nan"
        # poison grads with NaN; "train.loss.spike" scales loss AND
        # grads by a finite factor (the sentry's EWMA lever).
        from paddle_tpu.distributed import chaos
        self._poison_sites = tuple(
            s for s in ("trainer.grad", "train.grad.nan")
            if chaos.ENABLED and chaos.site_rate(s) > 0)
        self._chaos_poison = bool(self._poison_sites)
        chaos_spike = bool(chaos.ENABLED
                           and chaos.site_rate("train.loss.spike") > 0)
        names = []
        if self._chaos_poison:
            names.append("poison")
        if chaos_spike:
            names.append("spike")
        if cfg.health_probe:
            names.append("loss_cap")
        self._extra_names = tuple(names)

        loss_for = self._make_loss_for()
        grad_fn = jax.value_and_grad(
            lambda tp, fp, b: loss_for({**fp, **tp}, b))

        def step(params, opt_state, lr, batch, *extra):
            kw = dict(zip(names, extra))
            with self._precision_ctx():
                return _step_inner(params, opt_state, lr, batch, **kw)

        def _step_inner(params, opt_state, lr, batch, poison=None,
                        spike=None, loss_cap=None):
            train_p = {n: params[n] for n in self.param_names}
            frozen_p = {n: v for n, v in params.items()
                        if n not in train_p}
            if cfg.grad_accum_steps > 1:
                n_mb = cfg.grad_accum_steps

                def micro(carry, mb):
                    acc_loss, acc_g = carry
                    l, g = grad_fn(train_p, frozen_p, mb)
                    return (acc_loss + l,
                            jax.tree.map(jnp.add, acc_g, g)), None

                zeros = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), train_p)
                mbs = {k: v.reshape((n_mb, v.shape[0] // n_mb)
                                    + v.shape[1:])
                       for k, v in batch.items()}
                (loss_sum, grads), _ = jax.lax.scan(
                    micro, (jnp.zeros((), jnp.float32), zeros), mbs)
                loss = loss_sum / n_mb
                grads = jax.tree.map(lambda g: g / n_mb, grads)
            else:
                loss, grads = grad_fn(train_p, frozen_p, batch)
            if spike is not None:
                loss = loss * spike
                grads = jax.tree.map(lambda g: g * spike, grads)
            if poison is not None:
                grads = jax.tree.map(lambda g: g * poison, grads)
            return self._apply_update(loss, grads, params, opt_state,
                                      lr, loss_cap)

        return self._jit_step(step)

    def _precision_ctx(self):
        """The package-global matmul precision is 'highest' so EAGER f32
        numerics match the reference; inside the compiled low-precision
        train step that setting would run every bf16 matmul as multi-pass
        f32 emulation (several x slower on the MXU). bf16 compute with
        f32 accumulation is the intended training numerics."""
        import contextlib
        cfg = self.config
        low_prec = (cfg.compute_dtype is not None and
                    jnp.dtype(cfg.compute_dtype) in
                    (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)))
        return (jax.default_matmul_precision("default") if low_prec
                else contextlib.nullcontext())

    def _apply_update(self, loss, grads, params, opt_state, lr,
                      loss_cap=None):
        """Shared step epilogue: f32 grads + opt barrier + optimizer;
        with skip_nonfinite_grads the whole update is suppressed in-jit
        when any grad (or the loss) is Inf/NaN. With health_probe the
        suppression generalizes — non-finite OR loss above `loss_cap`
        — and the step additionally returns probe = [global_grad_norm,
        applied] (one more reduction; no extra host sync)."""
        grads = _opt_barrier(
            jax.tree.map(lambda g: g.astype(jnp.float32), grads),
            self.config)
        if self.config.offload_opt_state:
            # pull the parked moments into device memory for the update;
            # out_shardings park the new state back in pinned host
            opt_state = {
                n: {k: jax.device_put(
                    v, self._opt_leaf_sharding(n, v, "device"))
                    for k, v in st.items()}
                for n, st in opt_state.items()}
        train_p = {n: params[n] for n in self.param_names}
        with jax.named_scope("optimizer"):
            new_p, new_s = self.optimizer.apply_gradients_arrays(
                train_p, grads, opt_state, lr)
        if self.config.health_probe:
            # ONE global reduction: the squared grad norm propagates
            # any NaN/Inf, so isfinite(gnorm2) is the all-grads-finite
            # check and sqrt(gnorm2) the probe's grad-norm — the
            # detection rides values the step computes anyway
            gnorm2 = jnp.zeros((), jnp.float32)
            for g in grads.values():
                gnorm2 = gnorm2 + jnp.sum(
                    jnp.asarray(g, jnp.float32) ** 2)
            healthy = jnp.logical_and(jnp.isfinite(loss),
                                      jnp.isfinite(gnorm2))
            if loss_cap is not None:
                healthy = jnp.logical_and(healthy, loss <= loss_cap)
            new_p = {n: jnp.where(healthy, v, train_p[n])
                     for n, v in new_p.items()}
            new_s = jax.tree.map(
                lambda new, old: jnp.where(healthy, new, old),
                new_s, opt_state)
            out_params = dict(params)
            out_params.update(new_p)
            probe = jnp.stack([jnp.sqrt(gnorm2),
                               healthy.astype(jnp.float32)])
            return loss, out_params, new_s, probe
        if self.config.skip_nonfinite_grads:
            finite = jnp.isfinite(loss)
            for g in grads.values():
                finite = jnp.logical_and(finite,
                                         jnp.all(jnp.isfinite(g)))
            # select, don't branch: one program for both outcomes, and
            # every rank takes the same path by construction
            new_p = {n: jnp.where(finite, v, train_p[n])
                     for n, v in new_p.items()}
            new_s = jax.tree.map(lambda new, old: jnp.where(finite, new,
                                                            old),
                                 new_s, opt_state)
            out_params = dict(params)
            out_params.update(new_p)
            return loss, out_params, new_s, jnp.logical_not(finite)
        out_params = dict(params)
        out_params.update(new_p)
        return loss, out_params, new_s

    def _jit_step(self, step):
        """Shared jit wrapper: donation + param/opt-state shardings.
        Under offload_opt_state the opt-state in/out shardings carry
        memory_kind='pinned_host', so XLA schedules the H2D prefetch and
        the D2H writeback of the moments inside the step."""
        mesh = self.mesh
        donate = (0, 1) if self.config.donate else ()
        park = "pinned_host" if self.config.offload_opt_state else None
        if park:
            donate = (0,) if self.config.donate else ()
        # optional extra inputs (chaos poison/spike, sentry loss cap) /
        # output (skip flag or sentry probe)
        extra_in = (None,) * len(self._extra_names)
        has_extra_out = (self.config.skip_nonfinite_grads
                         or self.config.health_probe)
        if mesh is not None:
            pspec = {n: NamedSharding(mesh, self._spec(n))
                     for n in self.params}
            sspec = {n: {k: self._opt_leaf_sharding(n, v, park)
                         for k, v in st.items()}
                     for n, st in self.opt_state.items()}
            rep = NamedSharding(mesh, P())
            extra_out = (rep,) if has_extra_out else ()
            return jax.jit(
                step, donate_argnums=donate,
                in_shardings=(pspec, sspec, rep, None) + extra_in,
                out_shardings=(rep, pspec, sspec) + extra_out)
        if park:
            sspec = {n: {k: self._opt_leaf_sharding(n, v, park)
                         for k, v in st.items()}
                     for n, st in self.opt_state.items()}
            extra_out = (None,) if has_extra_out else ()
            return jax.jit(step, donate_argnums=donate,
                           in_shardings=(None, sspec, None, None)
                           + extra_in,
                           out_shardings=(None, None, sspec) + extra_out)
        return jax.jit(step, donate_argnums=donate)

    # -- public API --------------------------------------------------------
    def step(self, batch: dict) -> Tensor:
        """One optimizer step on `batch` (dict of np/jax arrays or Tensors).
        Returns the scalar loss as a lazy Tensor: steps dispatch
        asynchronously and only reading the value (float()/numpy()) blocks,
        so a caller that never reads it keeps the device queue full.

        The whole step is the step span `train.step` (step_num: the
        optimizer's step count before it), which any profiler capture
        holds; `train.step.place` and `.dispatch` are its children."""
        with observability.step_span("train.step",
                                     self.optimizer._step_count):
            return self._step(batch)

    def _step(self, batch: dict) -> Tensor:
        # numpy leaves stay numpy here: on the mesh path device_put
        # below does ONE direct host->sharded transfer (jnp.asarray
        # first paid an extra staging copy to the default device), and
        # on the meshless path jit dispatch converts identically
        batch = {k: (v._value if isinstance(v, Tensor)
                     else v if isinstance(v, (np.ndarray, jax.Array))
                     else jnp.asarray(v))
                 for k, v in batch.items()}
        if observability.ENABLED:
            self._telemetry_tick(batch)
        elif self._tel_last_t is not None:
            # telemetry was disabled mid-run: drop the stale timestamp
            # so a later re-enable doesn't report the whole disabled
            # gap as one giant step into train.step.seconds
            self._tel_last_t = self._tel_prev = None
        if self.mesh is not None:
            put = {}
            with observability.span("train.step.place"):
                for k, v in batch.items():
                    sh = self._batch_sharding(k, v.ndim)
                    if getattr(v, "sharding", None) == sh:
                        # already placed (the data_iter prefetch path):
                        # the hot path stays free of device_put — no
                        # H2D, no host->device sync on the dispatch
                        # thread
                        put[k] = v
                    else:
                        put[k] = jax.device_put(v, sh)
            batch = put
        if self._step_fn is None:
            self._step_fn = self._build_step(None)
        lrv = float(self._lr_value())  # lint: disable=hot-path-sync -- LR schedules are host-side python math, never a device value
        cache = getattr(self, "_lr_cache", None)
        if cache is None or cache[0] != lrv:
            # re-stage the lr scalar only when the schedule moves it:
            # no host->device transfer on the steady-state dispatch path
            self._lr_cache = (lrv, jnp.asarray(lrv, jnp.float32))
        args = (self.params, self.opt_state, self._lr_cache[1], batch)
        for extra in self._extra_names:
            if extra == "poison":
                from paddle_tpu.distributed import chaos
                v = 1.0
                if "trainer.grad" in self._poison_sites:
                    v *= chaos.grad_poison("trainer.grad")  # lint: disable=disabled-gate -- _extra_names is derived from chaos.ENABLED at trace time; with chaos off this input does not exist
                if "train.grad.nan" in self._poison_sites:
                    v *= chaos.grad_poison("train.grad.nan")  # lint: disable=disabled-gate -- same trace-time gate as above
                args += (jnp.asarray(v, jnp.float32),)
            elif extra == "spike":
                from paddle_tpu.distributed import chaos
                args += (jnp.asarray(
                    chaos.loss_spike("train.loss.spike"),  # lint: disable=disabled-gate -- same trace-time gate as above
                    jnp.float32),)
            else:   # "loss_cap" (sentry spike threshold)
                capv = self._loss_cap  # already a float (set_loss_cap)
                if self._cap_cache is None \
                        or self._cap_cache[0] != capv:
                    # restaged only when the sentry moves it (the
                    # sentry quantizes, so this is rare) — same
                    # host->device economy as the lr scalar above
                    self._cap_cache = (capv,
                                       jnp.asarray(capv, jnp.float32))
                args += (self._cap_cache[1],)
        # recompile attribution reads the jit trace-cache size around
        # the call: growth = a REAL retrace for this batch's shapes
        # (immune to observability being enabled mid-run, when already-
        # warm shapes must not recount)
        n0 = self._trace_count() if observability.ENABLED else None
        # enter the mesh context for the (first-call) trace so
        # sharding-aware custom vjps (e.g. the embedding grad reshard in
        # nn/functional/common.py) can read the axis names
        with self._mesh_ctx(), observability.span("train.step.dispatch"):
            out = self._step_fn(*args)
        if observability.ENABLED and n0 is not None \
                and self._trace_count() > n0:
            observability.inc("train.recompiles",
                              shape=self._batch_sig(batch))
        if self.config.health_probe:
            # the probe stays LAZY: [global_grad_norm, applied]; the
            # sentry (or any caller) decides when to pay the sync
            loss, self.params, self.opt_state, self.last_probe = out
        elif self.config.skip_nonfinite_grads:
            loss, self.params, self.opt_state, skipped = out
            self._note_skip(skipped)
        else:
            loss, self.params, self.opt_state = out
        self.optimizer._step_count += 1
        if self._tel_prev is not None:
            # hand the LAZY loss to the reporter: it materializes a
            # few steps later, when float() no longer forces a sync
            self._tel_prev[2] = loss
        return Tensor(loss, stop_gradient=True)

    def _batch_sharding(self, key, ndim):
        """Cached NamedSharding for batch leaf (key, ndim). step() used
        to rebuild the spec + NamedSharding per tensor per step — pure
        host work on the dispatch thread; the cache makes the repeat
        cost one dict hit, and hands the SAME objects to data_iter's
        prefetcher so placed batches compare equal in step()."""
        if self.mesh is None:
            return None           # prefetcher default-places; step()'s
            #                       jnp.asarray is then a no-op
        sh = self._batch_shardings.get((key, ndim))
        if sh is None:
            bspec = batch_spec(self.mesh.axis_names,
                               self.config.shard_batch_seq)
            spec = P(*(list(bspec) + [None] * (ndim - 2))[:ndim])
            sh = NamedSharding(self.mesh, spec)
            self._batch_shardings[(key, ndim)] = sh
        return sh

    def data_iter(self, loader, depth=2):
        """The idiomatic input-pipeline entry point: wrap a DataLoader
        (or any iterator of {name: array} batches) in a sharding-aware
        device prefetcher matched to this trainer. Batches come out
        already placed with the trainer's own batch shardings, H2D
        overlapped with the previous step's compute on a background
        thread, so step() performs ZERO device_put calls:

            for batch in trainer.data_iter(loader):
                loss = trainer.step(batch)

        Returns a DevicePrefetcher (io/prefetch.py): a context manager
        with close(), bounded to `depth` on-device batches."""
        from paddle_tpu.io.prefetch import DevicePrefetcher
        return DevicePrefetcher(loader, sharding_for=self._batch_sharding,
                                depth=depth)

    def _telemetry_tick(self, batch):
        """Report the PREVIOUS step's telemetry now that its interval
        is known (dispatch is async; the inter-call interval converges
        to device step time under donation backpressure), then stamp
        this step's token count for the next tick. One attribute check
        when observability is disabled (the caller gates)."""
        import time as _time
        now = _time.perf_counter()
        if self._telemetry is None:
            from paddle_tpu.observability.telemetry import (
                TrainingTelemetry)
            self._telemetry = TrainingTelemetry.for_model(self.model)
        if self._tel_prev is not None and self._tel_last_t is not None:
            tokens, seq, loss = self._tel_prev
            self._telemetry.step(tokens, now - self._tel_last_t,
                                 seq_len=seq, loss=loss)
        self._tel_last_t = now
        arr = batch.get("input_ids")
        if arr is None and batch:
            arr = next(iter(batch.values()))
        ndim = getattr(arr, "ndim", 0)
        if ndim >= 2:
            tokens = int(arr.shape[0]) * int(arr.shape[1])
            seq = int(arr.shape[1])
        elif ndim == 1:
            tokens = seq = int(arr.shape[0])
        else:
            tokens = seq = 0
        # the batch is GLOBAL; tokens_per_sec/MFU are catalogued
        # per-CHIP, so divide by the mesh size — otherwise a 4-chip
        # run reads 4x the true MFU
        if self.mesh is not None:
            tokens = tokens / max(1, int(self.mesh.devices.size))
        self._tel_prev = [tokens, seq, None]
        self._note_logits_bytes_saved(tokens)

    def _note_logits_bytes_saved(self, tokens):
        """With a blockwise-CE model config (loss_chunk > 0), publish
        the per-chip bytes of [B*S, vocab] logits the loss path avoids
        materializing this step — the memory evidence behind an MFU
        move (ISSUE 14). One getattr chain + gauge set per step,
        already inside the observability-gated telemetry tick."""
        mcfg = getattr(self.model, "config", None)
        chunk = getattr(mcfg, "loss_chunk", 0) or 0
        vocab = getattr(mcfg, "vocab_size", 0) or 0
        if not (chunk and vocab and tokens):
            return
        dt = self.config.compute_dtype
        itemsize = jnp.dtype(dt).itemsize if dt is not None else 4
        if observability.ENABLED:
            from paddle_tpu.kernels.blockwise_ce import logits_bytes_saved
            observability.set_gauge(
                "train.loss.logits_bytes_saved",
                logits_bytes_saved(
                    int(tokens), int(vocab), int(chunk),
                    int(getattr(mcfg, "loss_vocab_block", 0) or 0),
                    itemsize))

    def _trace_count(self):
        """Traced programs in the step's jit cache (0 before the step
        fn exists): step() compares before/after each call, so
        `train.recompiles` counts REAL retraces, labeled with the
        batch-shape signature that triggered them (the ROADMAP
        bucket-autotune feed). Cardinality is bounded by the
        pipeline's real shape buckets."""
        fn = self._step_fn
        return 0 if fn is None else int(fn._cache_size())

    @staticmethod
    def _batch_sig(batch):
        """The `shape` label for train.recompiles: every leaf's name,
        dims, and dtype, sorted — distinct signature = distinct trace."""
        return ",".join(
            f"{k}:{'x'.join(str(d) for d in getattr(v, 'shape', ()))}"
            f":{getattr(v, 'dtype', '?')}"
            for k, v in sorted(batch.items()))

    def fleet_heartbeat(self, store, rank, world_size, **kw):
        """Publish this process's training telemetry into the
        cross-rank heartbeat plane (observability/fleet.py): step,
        tokens/sec, MFU, recompiles and pending async saves land in
        the rendezvous store under ``fleet/hb/{rank}`` every couple of
        seconds, where the rank-0 aggregator (or a serving replica's
        ``GET /debug/fleet``) computes step skew and straggler flags.
        Returns the started FleetHeartbeat — or None when
        observability is disabled: no thread, no store traffic, the
        plane's zero-cost contract."""
        if not observability.ENABLED:
            return None
        from paddle_tpu.observability.fleet import FleetHeartbeat
        return FleetHeartbeat(store, rank, world_size, **kw).start()

    @property
    def telemetry(self):
        """The TrainingTelemetry reporter (None until a step ran with
        observability enabled)."""
        return self._telemetry

    def _note_skip(self, flag):
        """Track consecutive non-finite skips without a per-step host
        sync: flags buffer until nonfinite_check_every of them pend,
        then one blocking read drains the batch; crossing
        max_consecutive_nonfinite raises NonFiniteGradError (the run
        has diverged — checkpoint-and-abort beats skipping forever)."""
        self._pending_skips.append(flag)
        if len(self._pending_skips) < max(
                1, self.config.nonfinite_check_every):
            return
        pending, self._pending_skips = self._pending_skips, []
        for f in pending:
            if bool(np.asarray(f)):
                self.nonfinite_streak += 1
                self.nonfinite_skipped += 1
                if observability.ENABLED:
                    observability.inc("train.nonfinite_skips")
            else:
                self.nonfinite_streak = 0
        if self.nonfinite_streak >= self.config.max_consecutive_nonfinite:
            raise NonFiniteGradError(
                f"{self.nonfinite_streak} consecutive steps produced "
                f"non-finite gradients (limit "
                f"{self.config.max_consecutive_nonfinite}); aborting")

    def _mesh_ctx(self):
        import contextlib
        return self.mesh if self.mesh is not None \
            else contextlib.nullcontext()

    def _lr_value(self):
        return self.optimizer._lr_value() * self._lr_scale

    def set_lr_scale(self, scale):
        """Transient multiplier on the schedule's LR (1.0 = none) —
        the sentry's post-rollback dampening ramp. Host-side python
        math; the staged lr scalar re-transfers only when it moves."""
        self._lr_scale = float(scale)

    def set_loss_cap(self, cap):
        """The sentry's in-jit spike threshold (health_probe only): an
        update whose loss exceeds `cap` is suppressed inside the
        compiled step — params and optimizer state pass through
        unchanged — and the probe reports applied=0. +inf disarms."""
        self._loss_cap = float(cap)

    def lower(self, batch: dict):
        """jax.jit lowering of the step for inspection/AOT-compile."""
        if self._step_fn is None:
            self._step_fn = self._build_step(None)
        if observability.ENABLED:
            # an AOT lowering is a program build for this shape too
            observability.inc("train.recompiles",
                              shape=self._batch_sig(batch))
        lr = jnp.asarray(self._lr_value(), jnp.float32)
        args = (self.params, self.opt_state, lr, batch)
        for extra in self._extra_names:
            v = float("inf") if extra == "loss_cap" else 1.0
            args += (jnp.asarray(v, jnp.float32),)
        # same mesh context as step(): AOT lowering must see the ambient
        # mesh or sharding-aware vjps silently degrade
        with self._mesh_ctx():
            return self._step_fn.lower(*args)

    def _phase_twins(self, loss_for):
        """Forward-only and forward+backward twins of one loss closure
        — they mirror _step_inner EXACTLY, including the grad-accum
        microbatch scan, which is a different program (different peak
        memory / runtime) than one full-batch pass."""
        train_names = set(self.param_names)
        n_mb = self.config.grad_accum_steps

        def _split_mb(b):
            return {k: v.reshape((n_mb, v.shape[0] // n_mb)
                                 + v.shape[1:])
                    for k, v in b.items()}

        def fwd_fn(params, b):
            if n_mb > 1:
                def micro(acc, mb):
                    return acc + loss_for(params, mb), None
                tot, _ = jax.lax.scan(
                    micro, jnp.zeros((), jnp.float32), _split_mb(b))
                return tot / n_mb
            return loss_for(params, b)

        def fwdbwd_fn(params, b):
            tp = {n: params[n] for n in train_names}
            fp = {n: v for n, v in params.items() if n not in train_names}
            gfn = jax.value_and_grad(
                lambda t, mb: loss_for({**fp, **t}, mb))
            if n_mb > 1:
                def micro(carry, mb):
                    acc_l, acc_g = carry
                    l, g = gfn(tp, mb)
                    return (acc_l + l,
                            jax.tree.map(jnp.add, acc_g, g)), None
                zeros = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), tp)
                (ls, gs), _ = jax.lax.scan(
                    micro, (jnp.zeros((), jnp.float32), zeros),
                    _split_mb(b))
                return ls / n_mb, gs
            return gfn(tp, b)

        return fwd_fn, fwdbwd_fn

    def measure_phase_seconds(self, batch: dict, iters: int = 2):
        """Phase-attributed step timing: where does the step's wall
        time go? Compiles forward-only and forward+backward twins of
        the step's OWN loss machinery (`_make_loss_for` — same cast,
        batch constraint and precision context the fused step traces)
        and attributes

            fwd       = t(loss)
            bwd       = t(value_and_grad) - t(loss)
            optimizer = t(full step)      - t(value_and_grad)

        Each timing is a mean over `iters` synced runs after a compile
        warmup. Records `train.phase.seconds{phase=...}` when
        observability is enabled and always returns
        {"fwd", "bwd", "optimizer", "step"} seconds. NOTE: the
        full-step timing drives `iters + 1` REAL optimizer steps (the
        donated program is the thing being measured) — call this from
        a bench/diagnostic context, not mid-training-run.

        With overlap_fsdp active the twins gain a comm-attribution
        column: two extra twin pairs run — `propagated` (overlap
        forced off, XLA-propagated collectives) and `nocomm` (same
        program with the params REPLICATED over 'fsdp', so no weight
        all-gather exists) — and the result grows
        {"fwd_comm", "bwd_comm"} (collective seconds per phase:
        propagated − nocomm, the overlap-fraction denominator) and
        {"overlap_fraction"} (comm hidden under compute / total comm,
        via the `train.overlap.phase` trace spans all six timings are
        recorded to). The nocomm twin still carries the grad
        reduce over the batch axes in bwd, so the column attributes
        WEIGHT-movement comm, not every collective.
        """
        import time as _time
        batch = {k: (v._value if isinstance(v, Tensor)
                     else v if isinstance(v, (np.ndarray, jax.Array))
                     else jnp.asarray(v))
                 for k, v in batch.items()}
        if self.mesh is not None:
            batch = {k: jax.device_put(
                v, self._batch_sharding(k, v.ndim))
                for k, v in batch.items()}
        loss_for = self._make_loss_for()
        fwd_fn, fwdbwd_fn = self._phase_twins(loss_for)

        def _timed(run):
            # the warmup must DRAIN, not just dispatch: jit returns
            # after async dispatch, and an in-flight warmup execution
            # would bleed into the timed window
            jax.block_until_ready(run())
            t0 = _time.perf_counter()
            for _ in range(max(1, iters)):
                out = run()
            jax.block_until_ready(out)
            return (_time.perf_counter() - t0) / max(1, iters)

        with self._mesh_ctx():
            with self._precision_ctx():
                jf = jax.jit(fwd_fn)
                jg = jax.jit(fwdbwd_fn)
                t_fwd = _timed(lambda: jf(self.params, batch))
                t_fwdbwd = _timed(lambda: jg(self.params, batch))

        def _full():
            loss = self.step(batch)
            # close the dispatch chain so the timing covers execution
            return loss._value

        t_step = _timed(_full)
        phases = {
            "fwd": t_fwd,
            "bwd": max(0.0, t_fwdbwd - t_fwd),
            "optimizer": max(0.0, t_step - t_fwdbwd),
            "step": t_step,
        }
        overlap_on = (self.config.overlap_fsdp and self.mesh is not None
                      and "fsdp" in self.mesh.axis_names)
        if overlap_on:
            from paddle_tpu.observability import trace
            from paddle_tpu.parallel.overlap import (
                overlap_fraction_from_spans)
            # comm-attribution twins: `propagated` = same weights, ring
            # forced off (XLA-propagated collectives); `nocomm` = same
            # PROGRAM with params replicated over 'fsdp' (no weight
            # all-gather exists at all). propagated − nocomm isolates
            # weight-movement comm per phase; propagated − overlapped
            # is how much of it the ring hid.
            pf, pg = self._phase_twins(self._make_loss_for(overlap=False))
            nc_params = {
                n: jax.device_put(v, NamedSharding(
                    self.mesh, _strip_axis(self._spec(n), "fsdp")))
                for n, v in self.params.items()}
            with self._mesh_ctx():
                with self._precision_ctx():
                    jpf, jpg = jax.jit(pf), jax.jit(pg)
                    t_p_fwd = _timed(lambda: jpf(self.params, batch))
                    t_p_fb = _timed(lambda: jpg(self.params, batch))
                    # same jitted twins: new shardings = new cache entry
                    t_n_fwd = _timed(lambda: jpf(nc_params, batch))
                    t_n_fb = _timed(lambda: jpg(nc_params, batch))
            wall = _time.time()
            for variant, f, fb in (
                    ("overlapped", t_fwd, t_fwdbwd),
                    ("propagated", t_p_fwd, t_p_fb),
                    ("nocomm", t_n_fwd, t_n_fb)):
                trace.record_span("train.overlap.phase", wall, f * 1e6,
                                  attrs={"variant": variant,
                                         "phase": "fwd"})
                trace.record_span("train.overlap.phase", wall,
                                  max(0.0, fb - f) * 1e6,
                                  attrs={"variant": variant,
                                         "phase": "bwd"})
            frac = overlap_fraction_from_spans()
            phases["fwd_comm"] = max(0.0, t_p_fwd - t_n_fwd)
            phases["bwd_comm"] = max(
                0.0, (t_p_fb - t_p_fwd) - (t_n_fb - t_n_fwd))
            phases["overlap_fraction"] = frac
            if observability.ENABLED:
                observability.observe("train.overlap.comm.seconds",
                                      phases["fwd_comm"], phase="fwd")
                observability.observe("train.overlap.comm.seconds",
                                      phases["bwd_comm"], phase="bwd")
                if frac is not None:
                    observability.set_gauge("train.overlap.fraction",
                                            frac)
        if observability.ENABLED:
            observability.observe("train.phase.seconds", phases["fwd"],
                                  phase="fwd")
            observability.observe("train.phase.seconds", phases["bwd"],
                                  phase="bwd")
            observability.observe("train.phase.seconds",
                                  phases["optimizer"], phase="optimizer")
        return phases

    def sync_to_model(self):
        """Write the trainer's param arrays back into the Layer tree (for
        state_dict / checkpoint / eval through the eager API)."""
        tensors = state_tensors(self.model)
        for n, arr in self.params.items():
            tensors[n]._value = arr
        return self.model

    # -- checkpointing -----------------------------------------------------
    def checkpoint_state(self):
        """The state a training checkpoint must capture — params AND
        optimizer moments — as a nested dict save_state_dict flattens.
        Resuming params without moments silently restarts Adam's
        bias-correction warmup."""
        return {"params": dict(self.params),
                "opt": {n: dict(st) for n, st in self.opt_state.items()}}

    def save_checkpoint(self, path):
        """Save params + optimizer state into `path`, matching the
        elastic save boundary (run_resilient's ``save_fn(step, path)``
        is ``lambda step, path: trainer.save_checkpoint(path)``). With
        a `checkpointer` attached this returns after only the device->
        host snapshot — hashing and file I/O overlap the following
        steps, and donation is safe because the snapshot materializes
        before return. Without one, a plain synchronous save."""
        sd = self.checkpoint_state()
        if self.checkpointer is not None:
            self.checkpointer.save(sd, path)
        else:
            from paddle_tpu.distributed import checkpoint as ckpt_mod
            ckpt_mod.save_state_dict(sd, path)
        return path

    def load_checkpoint(self, path):
        """Restore params + optimizer state written by save_checkpoint,
        resharded to this trainer's current placements. Flushes the
        attached checkpointer first so an in-flight save of `path` is
        never half-read."""
        from paddle_tpu.distributed import checkpoint as ckpt_mod
        if self.checkpointer is not None:
            self.checkpointer.flush()
        sd = {"params": {n: Tensor(v) for n, v in self.params.items()},
              "opt": {n: {k: Tensor(v) for k, v in st.items()}
                      for n, st in self.opt_state.items()}}
        ckpt_mod.load_state_dict(sd, path)
        self.params = {n: t._value for n, t in sd["params"].items()}
        self.opt_state = {n: {k: t._value for k, t in st.items()}
                          for n, st in sd["opt"].items()}
        # loaded leaves arrive COMMITTED to their restore device, and
        # committed-ness is part of the jit cache key — left as-is, the
        # first step after every restore (elastic resume, sentry
        # rollback) silently retraces the whole program. Re-stage to
        # the same placement __init__ produced: the sharded path
        # re-runs _shard_state, the default path drops commitment by
        # round-tripping through host.
        if self.mesh is not None and self.plan is not None:
            self._shard_state()
        else:
            import numpy as np
            self.params = {n: jnp.asarray(np.asarray(v))
                           for n, v in self.params.items()}
            self.opt_state = {n: {k: jnp.asarray(np.asarray(v))
                                  for k, v in st.items()}
                              for n, st in self.opt_state.items()}
        return path
