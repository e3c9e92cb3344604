"""Compiled distributed training step.

This is the TPU replacement for the reference's fleet training loop
(dygraph forward → eager allreduce → optimizer): ONE jit-compiled XLA
program per step containing forward, backward, grad reduction, clipping and
the optimizer update, with params/optimizer state donated (updated in-place
in HBM) and every tensor sharded per the GSPMD plan. XLA overlaps the
collectives with compute on ICI.
"""
import time
import weakref
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..framework.core import Tensor
from ..nn.layer_base import functional_call, load_state_pytree
from ..profiler import _compiles_since, build_span, span
from .mesh import get_mesh
from .sharding_utils import plan_shardings

__all__ = ["Trainer", "LossBuffer", "shard_batch", "make_compute_loss",
           "batch_to_arrays"]

# consts key carrying the step counter that salts in-step RNG draws
_RNG_STEP = "__rng_step__"

# every live Trainer, so long-running harnesses (the tier-1 conftest's
# module-boundary GC hook) can trim per-signature compiled-step memos
# without plumbing handles — the ServeStats/_ENGINES registry pattern
_LIVE_TRAINERS = weakref.WeakSet()


def clear_compiled_step_memos():
    """Drop every live Trainer's per-signature compiled-program memos
    (`_placed_steps`/`_placed_multis`/`_batch_shardings`). The memos
    pin compiled executables (megabytes each, plus their jaxpr/HLO
    object graphs); a test-suite module that finished with its
    trainers no longer needs them, and anything still live simply
    recompiles on its next step. Returns the number of entries
    dropped. Used by tests/conftest.py at module boundaries (ROADMAP
    'tier-1 wall-clock health')."""
    n = 0
    for tr in list(_LIVE_TRAINERS):
        for memo in (tr._placed_steps, tr._placed_multis,
                     tr._batch_shardings):
            n += len(memo)
            memo.clear()
    return n


def make_compute_loss(model, loss_fn):
    """Pure (params, consts, batch) -> (fp32 loss, buffer_updates) via
    functional_call. Shared by Trainer and LocalSGDTrainer so loss/dtype
    handling can't drift.

    buffer_updates is {name: traced_value} for buffers whose ops attempted a
    state write during the trace (BatchNorm running stats): the caller folds
    them back into its consts so stats keep accumulating under jit."""
    from ..nn.layer_base import collect_buffer_updates

    def compute_loss(p, consts, batch):
        with collect_buffer_updates() as sink:
            with functional_call(model, {**p, **consts}):
                loss = loss_fn(model, batch)
        updates = {}
        if sink:
            by_id = {id(b): name for name, b in model.named_buffers()}
            for tid, (_, val) in sink.items():
                name = by_id.get(tid)
                if name is not None:
                    updates[name] = val
        lv = loss._value if isinstance(loss, Tensor) else loss
        return lv.astype(jnp.float32), updates
    return compute_loss


def batch_to_arrays(batch):
    """Tensor leaves -> raw arrays, for any pytree-shaped batch."""
    return jax.tree_util.tree_map(
        lambda v: v._value if isinstance(v, Tensor) else jnp.asarray(v),
        batch, is_leaf=lambda x: isinstance(x, Tensor))


def shard_batch(batch, mesh=None, spec=("dp", "fsdp")):
    """device_put a batch pytree with its leading dim sharded over data axes.

    Axes that don't divide the batch dim are dropped (replicated) so user
    batches of any size are accepted, mirroring `sharding_utils.constraint`."""
    from ..io.prefetch import _leaf_arrays, batch_shardings
    mesh = mesh or get_mesh()
    arrays = _leaf_arrays(batch)
    return jax.device_put(arrays, batch_shardings(arrays, mesh, spec))


class LossBuffer:
    """Async metrics drain: `Trainer.step` returns an UNFETCHED device
    loss — calling `float(loss)` every step blocks the host on step N and
    stalls dispatch of N+1 (the dispatch-queue bubble docs/performance.md
    rule 4 warns about). A LossBuffer holds the unfetched losses and
    syncs ONCE per `drain_every` appended STEPS, so the host keeps
    running ahead of the device.

        buf = LossBuffer(drain_every=10)
        for batch in loader:
            buf.append(trainer.step(batch))   # no host sync here
        print(buf.drain())                    # final sync + last loss

    Appends accept both a scalar device loss (`Trainer.step`) and a
    length-N horizon loss vector (`Trainer.step_multi`) — a vector
    counts as N steps toward `drain_every` and drains in step order, so
    mixed per-step / multi-step loops share one buffer. `maxlen` bounds
    the drained-history list; `fetches` counts REAL host syncs
    (observability: it must stay ~steps/drain_every)."""

    def __init__(self, drain_every=16, maxlen=65536):
        self.drain_every = max(1, int(drain_every))
        self.maxlen = maxlen
        self._pending = []
        self._pending_steps = 0
        self.losses = []     # drained python floats, oldest first
        self.fetches = 0     # number of host syncs issued

    @staticmethod
    def _steps_of(loss):
        """1 for a scalar loss, N for a [N] horizon vector — read from
        shape metadata only (never fetches)."""
        shape = getattr(loss, "shape", ())
        return int(shape[0]) if shape else 1

    def append(self, loss):
        self._pending.append(loss)
        self._pending_steps += self._steps_of(loss)
        if self._pending_steps >= self.drain_every:
            self.drain()
        return self

    @property
    def pending(self):
        """Dispatched-but-unfetched loss (step) count."""
        return self._pending_steps

    @property
    def last(self):
        """Most recently DRAINED loss (no sync), or None."""
        return self.losses[-1] if self.losses else None

    def drain(self):
        """Fetch every pending loss in one host sync; returns the latest
        loss value. Horizon vectors flatten in append order, so the
        drained stream is the per-step loss sequence regardless of how
        the steps were dispatched."""
        if self._pending:
            vals = jax.device_get(self._pending)
            self.fetches += 1
            for v in vals:
                arr = np.asarray(v)
                if arr.ndim:
                    self.losses.extend(float(x) for x in arr)
                else:
                    self.losses.append(float(arr))
            self._pending = []
            self._pending_steps = 0
            if self.maxlen and len(self.losses) > self.maxlen:
                del self.losses[:len(self.losses) - self.maxlen]
        return self.last

    def __len__(self):
        return len(self.losses) + self._pending_steps


class Trainer:
    """Owns the sharded params/opt-state and the compiled step.

        trainer = Trainer(model, optimizer, loss_fn)   # loss_fn(model, batch)
        loss = trainer.step(batch)                      # batch: dict of arrays
    """

    @build_span("trainer.build")
    def __init__(self, model, optimizer, loss_fn, mesh=None, donate=True,
                 grad_accum_steps=1, grad_transform=None,
                 batch_spec=("dp", "fsdp"), dp_overlap="off",
                 dp_overlap_buckets=2):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or get_mesh()
        self.grad_accum_steps = grad_accum_steps
        # dp grad-reduction dispatch: 'off' leaves the reduction to
        # GSPMD (one bulk all-reduce after the whole backward), 'bulk'
        # issues an explicit per-parameter-BUCKET shard_map psum, 'ring'
        # the chunked ascending ring (ops/overlap.py) — each bucket's
        # wire overlaps the optimizer update consuming the previous
        # bucket, and 'ring' is bit-identical to 'bulk' by the twin
        # pin. The shard_map is manual over 'dp' alone (axis_names), so
        # the other mesh axes stay with GSPMD.
        if dp_overlap not in ("off", "bulk", "ring"):
            raise ValueError(f"dp_overlap must be 'off', 'bulk' or "
                             f"'ring', got {dp_overlap!r}")
        if dp_overlap != "off" and grad_transform is not None:
            raise ValueError("dp_overlap decomposes the grad reduction "
                             "per bucket; grad_transform expects the "
                             "whole tree — use one or the other")
        self.dp_overlap = dp_overlap
        self.dp_overlap_buckets = int(dp_overlap_buckets)
        # grad_transform(grads, state) -> (grads, state): gradient
        # compression/filtering between backward and the optimizer (DGC
        # error-feedback sparsification, bf16 cast, custom clipping) —
        # reference fleet meta_optimizers dgc/fp16_allreduce. State (e.g.
        # DGC residuals) is carried inside the compiled step, donated like
        # optimizer slots.
        self.grad_transform = grad_transform
        self._plan = plan_shardings(model, self.mesh)

        trainable, consts = {}, {}
        for name, p in model.named_parameters():
            v = jax.device_put(p._value, self._plan[name])
            (consts if p.stop_gradient else trainable)[name] = v
        for name, b in model.named_buffers():
            consts[name] = jax.device_put(b._value, self._plan[name])
        # per-step RNG salt rides consts so stochastic layers (dropout,
        # noisy MoE gates) draw FRESH randomness every compiled step
        # (framework.random.traced_salt); load_state_pytree ignores it.
        # Mesh-placed like every other const so the whole consts tree has
        # one device assignment (required for the in_shardings step below)
        consts[_RNG_STEP] = jax.device_put(
            jnp.zeros((), jnp.uint32),
            NamedSharding(self.mesh, PartitionSpec()))
        self.params = trainable
        self.consts = consts
        self.opt_state = jax.jit(
            optimizer.init_state_pytree,
            out_shardings=self._state_shardings(
                jax.eval_shape(optimizer.init_state_pytree, self.params))
        )(self.params)
        if self.grad_transform is not None and \
                hasattr(self.grad_transform, "init_state"):
            self.gt_state = self._mesh_place(
                jax.jit(self.grad_transform.init_state)(self.params))
        else:
            self.gt_state = None
        self._donate = donate
        self._step_fn = self._build(donate)
        self._host_step = 0
        # batch placement: precomputed NamedSharding pytrees + specialized
        # compiled steps, keyed by the batch's (structure, shapes, dtypes)
        # signature. The specialized step pins the batch argument's
        # in_shardings, so the compiled program expects the batch already
        # laid out over the data axes — no replicate-then-reshard inside
        # jit, and host-numpy vs device-resident feeds share ONE program.
        self._batch_spec = tuple(batch_spec)
        self._batch_shardings = {}
        self._placed_steps = {}
        # fused multi-step programs, keyed by the STACKED batch signature
        # (which encodes the horizon length N in the leading dim)
        self._placed_multis = {}
        # FLIGHT RECORDER (serving.trace.FlightRecorder, shared schema
        # with the serving engines): off by default — attach_recorder
        # turns `step`s and step_multi horizons into tick records with
        # predicted vs measured drift accounting. Every hook is a dead
        # `if self.recorder is not None` branch.
        self.recorder = None
        self._rec_predicted_step_s = None
        self._rec_predicted_serial_s = None
        self._rec_last_t = None
        _LIVE_TRAINERS.add(self)

    def attach_recorder(self, recorder, predicted_step_s=None,
                        predicted_serial_step_s=None):
        """Attach a `serving.trace.FlightRecorder` (or True for a
        default one): every `step_multi` horizon — and every `step`,
        as a horizon of one under the shape ("step", 1) — records a
        "train" tick — N steps, measured dispatch-to-dispatch wall
        seconds,
        and (when `predicted_step_s` is given, normally
        `cost_model.roofline_step_time(...).step_s` or the schedule
        pass's overlap-aware `overlap_step_s`) the roofline-predicted
        horizon cost, feeding the same drift ledger the serving
        engines use (`ROOFLINE-DRIFT` / `debug.serving_report`).
        `predicted_serial_step_s` (normally the schedule pass's
        `serial_step_s` — the compute+wire sum with nothing
        overlapped) stamps the serial band next to it, so an
        over-drifting shape gets the serialized-vs-mispriced verdict
        instead of a blanket "re-fit the legs". Returns the
        recorder."""
        if recorder is True:
            from ..serving.trace import FlightRecorder
            recorder = FlightRecorder()
        self.recorder = recorder
        self._rec_predicted_step_s = predicted_step_s
        self._rec_predicted_serial_s = predicted_serial_step_s
        self._rec_last_t = None
        if recorder is not None:
            recorder.meta.update(engine="Trainer",
                                 donate=bool(self._donate))
        return recorder

    def mark_recorder_idle(self):
        """Tell the recorder the loop is about to do non-training host
        work (eval pass, checkpoint save, data stall): the next
        horizon's dispatch-to-dispatch gap would book that pause as
        horizon time, so it is measured from the dispatch call instead
        and kept OUT of the drift ledger — the trainer's rendering of
        the serving engines' polluted-window exclusion."""
        self._rec_last_t = None

    def _state_shardings(self, state):
        """Shardings for an optimizer-state pytree: a slot shaped like its
        parameter (moments, master copy) is laid out like the parameter,
        everything else (step counters, scalars) is replicated. Left to
        the compiler, a zeros-initialised slot depends on no input and
        comes out replicated on every device."""
        rep = NamedSharding(self.mesh, PartitionSpec())

        def pick(path, leaf):
            keys = [getattr(k, "key", None) for k in path]
            p = self.params.get(keys[1]) if len(keys) > 1 and \
                keys[0] == "slots" else None
            if p is not None and p.shape == leaf.shape:
                return p.sharding
            return rep
        return jax.tree_util.tree_map_with_path(pick, state)

    def _mesh_place(self, tree):
        """Replicate any single-device leaf onto the full mesh. A state
        leaf that depends on NO parameter (e.g. a stateless optimizer's
        bare step counter) gets its params pruned from the init jit, which
        then executes on one device — mixing that with mesh-committed
        params in a single step program is an invalid device assignment."""
        if self.mesh.devices.size <= 1:
            return tree
        rep = NamedSharding(self.mesh, PartitionSpec())

        def fix(v):
            sh = getattr(v, "sharding", None)
            if sh is not None and getattr(sh, "num_devices", 1) == 1:
                return jax.device_put(v, rep)
            return v
        return jax.tree_util.tree_map(fix, tree)

    def _build_body(self):
        """The ONE single-step body: (params, opt_state, gt_state,
        consts, lr, batch) -> (params, opt_state, gt_state, consts,
        fp32 loss). `step()`'s jit wraps it directly and every tick of
        `step_multi`'s fused scan runs it under the scan carry — the
        same closure, so the two paths cannot drift (the serving
        `_forward_tokens` pattern). Callers apply the per-step RNG salt
        (`traced_salt`) themselves: the jit wrapper once, the scan once
        per tick with the carried counter."""
        model, optimizer, loss_fn = self.model, self.optimizer, self.loss_fn
        accum = self.grad_accum_steps

        compute_loss = make_compute_loss(model, loss_fn)

        grad_transform = self.grad_transform

        def _local_grads(params, consts, batch):
            if accum <= 1:
                (loss_v, buf_updates), grads = jax.value_and_grad(
                    compute_loss, has_aux=True)(params, consts, batch)
            else:
                # gradient merge (reference DistributedStrategy.gradient_merge):
                # microbatch scan accumulating mean grads before ONE update
                micro = jax.tree_util.tree_map(
                    lambda v: v.reshape((accum, v.shape[0] // accum) + v.shape[1:]),
                    batch)

                def body(carry, mb):
                    loss_acc, grad_acc = carry
                    (lv, bu), g = jax.value_and_grad(
                        compute_loss, has_aux=True)(params, consts, mb)
                    grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, g)
                    return (loss_acc + lv, grad_acc), bu

                zeros = jax.tree_util.tree_map(
                    lambda v: jnp.zeros(v.shape, jnp.float32), params)
                (loss_sum, grad_sum), bus = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), zeros), micro)
                loss_v = loss_sum / accum
                grads = jax.tree_util.tree_map(lambda g: g / accum, grad_sum)
                # per-microbatch stat updates all start from the same consts;
                # carry the last microbatch's
                buf_updates = jax.tree_util.tree_map(lambda v: v[-1], bus)
            return loss_v, grads, buf_updates

        def _inner(params, opt_state, gt_state, consts, lr, batch):
            loss_v, grads, buf_updates = _local_grads(params, consts, batch)
            if grad_transform is not None:
                grads, gt_state = grad_transform(grads, gt_state)
            new_params, new_state = optimizer.apply_gradients_pytree(
                params, grads, opt_state, lr)
            new_consts = {**consts, **buf_updates}
            if _RNG_STEP in consts:
                new_consts[_RNG_STEP] = consts[_RNG_STEP] + 1
            return new_params, new_state, gt_state, new_consts, loss_v

        dp = int(self.mesh.shape.get("dp", 1))
        if self.dp_overlap == "off" or dp <= 1:
            return _inner

        # dp-overlap path: per-shard grads under an explicit shard_map
        # over 'dp', the grad reduction decomposed per parameter BUCKET
        # and interleaved with the optimizer update consuming each
        # bucket — bucket b's ring steps share no data edge with bucket
        # b-1's update dots, so the two-stream schedule (and the chip)
        # overlap them. The local loss/grads are per-shard MEANS, so the
        # global ones are sum/dp — reduced with the same ascending fold
        # ('ring') or bulk psum ('bulk'), bit-identical by the twin pin.
        from jax.sharding import PartitionSpec as P
        from ..ops.overlap import chunked_all_reduce
        impl = "ring" if self.dp_overlap == "ring" else "bulk"
        n_buckets = max(1, self.dp_overlap_buckets)
        mesh = self.mesh

        def _shard_body(params, opt_state, gt_state, consts, lr, batch):
            loss_v, grads, buf_updates = _local_grads(params, consts, batch)
            names = sorted(grads)
            nb = max(1, min(n_buckets, len(names)))
            bounds = [(i * len(names)) // nb for i in range(nb + 1)]
            new_params, new_slots = {}, {}
            new_step = opt_state["step"]
            for i in range(nb):
                bucket = names[bounds[i]:bounds[i + 1]]
                if not bucket:
                    continue
                gb = {n: chunked_all_reduce(grads[n], "dp", impl=impl) / dp
                      for n in bucket}
                up, us = optimizer.apply_gradients_pytree(
                    {n: params[n] for n in bucket}, gb,
                    {"slots": {n: opt_state["slots"][n] for n in bucket},
                     "step": opt_state["step"]}, lr)
                new_params.update(up)
                new_slots.update(us["slots"])
                new_step = us["step"]
            loss_v = chunked_all_reduce(loss_v, "dp", impl=impl) / dp
            buf_updates = jax.tree_util.tree_map(
                lambda v: (chunked_all_reduce(v, "dp", impl=impl) / dp
                           if jnp.issubdtype(jnp.asarray(v).dtype,
                                             jnp.floating) else v),
                buf_updates)
            new_consts = {**consts, **buf_updates}
            if _RNG_STEP in consts:
                new_consts[_RNG_STEP] = consts[_RNG_STEP] + 1
            new_state = {"slots": new_slots, "step": new_step}
            return new_params, new_state, gt_state, new_consts, loss_v

        def _inner_dp(params, opt_state, gt_state, consts, lr, batch):
            return jax.shard_map(
                _shard_body, mesh=mesh,
                in_specs=(P(), P(), P(), P(), P(), P("dp")),
                out_specs=(P(), P(), P(), P(), P()),
                axis_names={"dp"}, check_vma=False)(
                params, opt_state, gt_state, consts, lr, batch)

        return _inner_dp

    def _build(self, donate, in_shardings=None):
        _inner = self._build_body()

        def step(params, opt_state, gt_state, consts, lr, batch):
            from ..framework.random import traced_salt
            with traced_salt(consts.get(_RNG_STEP)):
                return _inner(params, opt_state, gt_state, consts, lr, batch)

        kwargs = {}
        if in_shardings is not None:
            kwargs["in_shardings"] = in_shardings
            # pin outputs to the same layout: step N's outputs then carry
            # shardings EQUAL to step N+1's pinned inputs, so the dispatch
            # cache hits from the first step onward (without this, the
            # first step's GSPMD-typed outputs force one extra compile)
            state_sh = in_shardings[:4]
            kwargs["out_shardings"] = state_sh + (
                NamedSharding(self.mesh, PartitionSpec()),)   # fp32 loss
        return jax.jit(step, donate_argnums=(0, 1, 2, 3) if donate else (),
                       **kwargs)

    def _build_multi(self, donate, in_shardings=None):
        """N train steps fused into ONE jitted lax.scan over a
        leading-stacked batch pytree ([N, ...] leaves) and an [N] lr
        vector, params/opt-state/grad-transform-state/consts threaded
        through the donated carry. The scan body is `_build_body()` —
        the SAME closure `step()` compiles — so fused and per-step loops
        cannot drift. Returns the length-N loss vector UNFETCHED: host
        contact happens only when the caller drains it."""
        _inner = self._build_body()

        def multi_step(params, opt_state, gt_state, consts, lrs, batches):
            from ..framework.random import traced_salt

            def tick(carry, xs):
                params, opt_state, gt_state, consts = carry
                lr, batch = xs
                with traced_salt(consts.get(_RNG_STEP)):
                    p, o, g, c, loss = _inner(params, opt_state, gt_state,
                                              consts, lr, batch)
                return (p, o, g, c), loss

            carry = (params, opt_state, gt_state, consts)
            (params, opt_state, gt_state, consts), losses = jax.lax.scan(
                tick, carry, (lrs, batches))
            return params, opt_state, gt_state, consts, losses

        kwargs = {}
        if in_shardings is not None:
            kwargs["in_shardings"] = in_shardings
            state_sh = in_shardings[:4]
            kwargs["out_shardings"] = state_sh + (
                NamedSharding(self.mesh, PartitionSpec()),)  # [N] f32 losses
        return jax.jit(multi_step,
                       donate_argnums=(0, 1, 2, 3) if donate else (),
                       **kwargs)

    # -- batch placement ----------------------------------------------------

    def place_batch(self, batch):
        """Normalize a batch onto the mesh with the precomputed GSPMD batch
        sharding (leading dim over the data axes). Host numpy / Tensor
        leaves are device_put — sharded and committed; already-resident
        leaves (`io.DeviceLoader` / `shard_batch` output) pass through
        untouched, since device_put with a matching sharding is a no-op.
        Every feed path therefore reaches the compiled step with identical
        input shardings: ONE compilation, zero per-step reshards."""
        from ..io.prefetch import (_leaf_arrays, batch_shardings,
                                   batch_signature)
        arrays = _leaf_arrays(batch)
        sig = batch_signature(arrays)
        sh = self._batch_shardings.get(sig)
        if sh is None:
            sh = batch_shardings(arrays, self.mesh, self._batch_spec)
            self._batch_shardings[sig] = sh
        return jax.device_put(arrays, sh), sig, sh

    def _placed_step(self, sig, batch_sh):
        """Compiled step specialized to one batch signature, with every
        argument's sharding pinned via in_shardings (batch included — the
        program is compiled to CONSUME the sharded batch, not to reshard a
        replicated one). Falls back to the generic jit when a sharding
        can't be derived (exotic state pytrees)."""
        fn = self._placed_steps.get(sig)
        if fn is None:
            try:
                leaf_sh = lambda v: v.sharding  # noqa: E731
                in_sh = (
                    jax.tree_util.tree_map(leaf_sh, self.params),
                    jax.tree_util.tree_map(leaf_sh, self.opt_state),
                    (jax.tree_util.tree_map(leaf_sh, self.gt_state)
                     if self.gt_state is not None else None),
                    jax.tree_util.tree_map(leaf_sh, self.consts),
                    NamedSharding(self.mesh, PartitionSpec()),   # lr scalar
                    batch_sh)
                fn = self._build(self._donate, in_shardings=in_sh)
            except (AttributeError, TypeError) as e:
                # a state leaf with no .sharding (exotic pytree): fall
                # back to the unpinned jit — LOUDLY, because the fallback
                # re-introduces the in-jit batch reshard this class
                # exists to avoid
                import warnings
                warnings.warn(
                    "Trainer: could not derive in_shardings for the "
                    f"compiled step ({e!r}); falling back to the "
                    "unpinned jit (batch resharding inside the step)")
                fn = self._step_fn
            self._placed_steps[sig] = fn
        return fn

    def place_horizon(self, batches):
        """Normalize a training horizon onto the mesh: `batches` is
        either a list/tuple of N per-step batch pytrees (host numpy or
        device-resident — stacked here, `io.prefetch.stack_batches`) or
        an already leading-stacked pytree (`DeviceLoader.stack(n)`
        output). Leaves land as [N, B, ...] arrays with the scan dim
        replicated and the per-step batch dim sharded over the data axes
        — the layout the fused scan pins as its batch in_shardings, so
        every feed path hits ONE compiled program per (N, signature)."""
        from ..io.prefetch import (_leaf_arrays, batch_signature,
                                   horizon_shardings, stack_batches)
        if isinstance(batches, (list, tuple)):
            arrays = stack_batches(batches)
        else:
            arrays = _leaf_arrays(batches)
        sig = ("multi", batch_signature(arrays))
        sh = self._batch_shardings.get(sig)
        if sh is None:
            sh = horizon_shardings(arrays, self.mesh, self._batch_spec)
            self._batch_shardings[sig] = sh
        return jax.device_put(arrays, sh), sig, sh

    def _placed_multi(self, sig, horizon_sh):
        """Compiled fused-scan step specialized to one stacked-batch
        signature (the horizon length N rides in the signature's leading
        dim), shardings pinned like `_placed_step` (same fallback
        contract when a state leaf has no derivable sharding)."""
        fn = self._placed_multis.get(sig)
        if fn is None:
            try:
                leaf_sh = lambda v: v.sharding  # noqa: E731
                rep = NamedSharding(self.mesh, PartitionSpec())
                in_sh = (
                    jax.tree_util.tree_map(leaf_sh, self.params),
                    jax.tree_util.tree_map(leaf_sh, self.opt_state),
                    (jax.tree_util.tree_map(leaf_sh, self.gt_state)
                     if self.gt_state is not None else None),
                    jax.tree_util.tree_map(leaf_sh, self.consts),
                    rep,                                 # [N] lr vector
                    horizon_sh)
                fn = self._build_multi(self._donate, in_shardings=in_sh)
            except (AttributeError, TypeError) as e:
                import warnings
                warnings.warn(
                    "Trainer: could not derive in_shardings for the "
                    f"fused multi-step program ({e!r}); falling back to "
                    "the unpinned jit (batch resharding inside the scan)")
                fn = self._build_multi(self._donate)
            self._placed_multis[sig] = fn
        return fn

    def _horizon_lrs(self, n):
        """Precompute the next `n` per-step learning rates HOST-SIDE by
        advancing the real scheduler — `get_lr()` then `sched.step()`
        per tick, exactly what n calls of `step()` would do — so
        warmup/decay boundaries falling MID-horizon feed the scan the
        same lr sequence the per-step loop would see."""
        sched = self.optimizer._lr_scheduler
        lrs = []
        for _ in range(int(n)):
            lrs.append(float(self.optimizer.get_lr()))
            if sched is not None:
                sched.step()
        return np.asarray(lrs, np.float32)

    def step_multi(self, batches, lrs=None):
        """Dispatch N train steps as ONE compiled `lax.scan`
        (`_build_multi`): one host dispatch per horizon instead of per
        step, donated state threaded through the carry, per-step lrs
        precomputed host-side (default: the optimizer's scheduler,
        advanced exactly as N `step()` calls would). `batches` is a
        list of N batch pytrees or a leading-stacked pytree
        (`DeviceLoader.stack(n)`). NON-BLOCKING: returns the [N] fp32
        loss vector unfetched — drain it through a `LossBuffer` (vector
        appends are supported) so host contact stays at horizon
        boundaries."""
        step = self._host_step
        with span("trainer.step", step=step):
            with span("trainer.place_batch", step=step):
                arrays, sig, horizon_sh = self.place_horizon(batches)
            n = jax.tree_util.tree_leaves(arrays)[0].shape[0]
            if lrs is None:
                lrs = self._horizon_lrs(n)
            else:
                lrs = np.asarray(lrs, np.float32)
                if lrs.shape != (n,):
                    raise ValueError(
                        f"step_multi: lrs shape {lrs.shape} != ({n},)")
                # parity with step(batch, lr=x), which advances the
                # scheduler even under an explicit lr: N explicit-lr steps
                # leave the scheduler N positions further along
                sched = self.optimizer._lr_scheduler
                if sched is not None:
                    for _ in range(int(n)):
                        sched.step()
            t0 = time.perf_counter() if self.recorder is not None else None
            fn = self._placed_multi(sig, horizon_sh)
            with span("trainer.dispatch", step=step):
                (self.params, self.opt_state, self.gt_state, self.consts,
                 losses) = fn(
                    self.params, self.opt_state, self.gt_state, self.consts,
                    jnp.asarray(lrs), arrays)
            # horizon-aware step accounting: state()/load_state round-trip
            # the TRUE device step count, not the host dispatch count
            self._host_step += int(n)
            if self.recorder is not None:
                self._record_tick(("train", int(n)), int(n), t0)
            return losses

    def _record_tick(self, shape, n, t0):
        """One dispatched horizon of `n` steps into the recorder.
        Called only with a recorder attached.

        Dispatch is NON-blocking, so the call's own wall time is not
        the horizon's: in a steady-state loop the dispatch-to-dispatch
        gap is (the next dispatch blocks on the donated carry), so
        measure that. The FIRST horizon after attach or
        mark_recorder_idle() has no previous dispatch — its call wall
        (from `t0`) is recorded but kept out of the drift ledger (cold
        compiles and host pauses are pollution, the same exclusion the
        serving engines apply to prefill windows), and so is a horizon
        whose call lowered, compiled or loaded a program (the compile
        log's entries since `t0`: a new signature, whichever memo holds
        it)."""
        now = time.perf_counter()
        steady = self._rec_last_t is not None and not _compiles_since(t0)
        # the tick's chrome slice must span the window it measured:
        # steady ticks start at the PREVIOUS dispatch, not this one
        start = self._rec_last_t if self._rec_last_t is not None else t0
        self._rec_last_t = now
        pred = self._rec_predicted_step_s
        serial = self._rec_predicted_serial_s
        self.recorder.tick(
            "train", shape, now - start, ts=start,
            predicted_s=(pred * n) if pred else None,
            predicted_serial_s=(serial * n) if serial else None,
            drift=steady, k=n, decode_rows=0, prefill_rows=0)

    def lower_step(self, batch, lr=0.0):
        """Lower the SAME specialized program `step()` dispatches for this
        batch's signature (in/out shardings pinned) — the honest target
        for static analysis, HLO pins, and memory audits. `_step_fn` (the
        unspecialized jit) exists only as the fallback for state pytrees
        whose shardings can't be derived; don't analyze that one."""
        arrays, sig, batch_sh = self.place_batch(batch)
        fn = self._placed_step(sig, batch_sh)
        return fn.lower(self.params, self.opt_state, self.gt_state,
                        self.consts, lr, arrays)

    def analysis_program(self, batch, lr=0.0, n=None):
        """Graph Doctor view of the SAME specialized step `step()`
        dispatches: one trace yields the StableHLO text AND jaxpr, plus
        per-argument capture of role (param / opt_state / gt_state /
        const / lr / batch), sharding (shard count per leaf, from the
        pinned in_shardings), and donation — everything the memory and
        sharding passes need for per-device peak-HBM estimation and
        replication lint that the HLO text alone can't recover.

        With `n` the FUSED multi-step program (`step_multi`, N ticks in
        one lax.scan over `batch` stacked N deep) is traced instead —
        the HOST-SYNC-TRAIN rule checks it for host transfers, donated
        carry, and a real device loop."""
        from ..analysis.lowering import LoweredProgram, tree_arg_infos
        if n:
            stacked = [batch] * int(n)
            arrays, sig, batch_sh = self.place_horizon(stacked)
            fn = self._placed_multi(sig, batch_sh)
            lrs = jnp.full((int(n),), float(lr), jnp.float32)
            traced = fn.trace(self.params, self.opt_state, self.gt_state,
                              self.consts, lrs, arrays)
            lr_arg, name = lrs, f"train_multi_n{int(n)}"
        else:
            arrays, sig, batch_sh = self.place_batch(batch)
            fn = self._placed_step(sig, batch_sh)
            traced = fn.trace(self.params, self.opt_state, self.gt_state,
                              self.consts, lr, arrays)
            lr_arg, name = lr, "train_step"
        donate = bool(self._donate)
        infos = tree_arg_infos(self.params, "param", donated=donate)
        infos += tree_arg_infos(self.opt_state, "opt_state",
                                donated=donate)
        if self.gt_state is not None:
            infos += tree_arg_infos(self.gt_state, "gt_state",
                                    donated=donate)
        infos += tree_arg_infos(self.consts, "const", donated=donate)
        infos += tree_arg_infos(lr_arg, "lr")
        infos += tree_arg_infos(arrays, "batch", shardings=batch_sh)
        return LoweredProgram(traced.lower().as_text(),
                              jaxpr=traced.jaxpr, name=name,
                              arg_infos=infos)

    def suggest_config(self, batch, hbm_budget=None, **kw):
        """Static config advice for THIS trainer: candidate microbatch
        sizes x remat policies ranked by roofline-predicted throughput,
        HBM-infeasible points pruned — one CPU trace per batch size, a
        what-if liveness replay per policy, zero compiles, zero device
        work (analysis/autotune.py). Returns an AutotuneReport whose
        `.best` names the config to measure first and whose `.advice`
        lines read "remat=dots: peak X → Y per device, +Z% recompute
        FLOPs"."""
        from ..analysis.autotune import autotune
        return autotune(self, batch, hbm_budget=hbm_budget, **kw)

    def step(self, batch, lr=None):
        """Dispatch one compiled step. NON-BLOCKING: the returned loss is
        an unfetched device array — `float()` it only when you must (or
        batch the syncs through a `LossBuffer`), so dispatch of step N+1
        overlaps step N's compute. Under a profiler session the step
        is a `trainer.step` span with `trainer.place_batch` and
        `trainer.dispatch` inside (docs/observability.md); with a
        recorder attached it records a tick like `step_multi`."""
        step = self._host_step
        with span("trainer.step", step=step):
            t0 = time.perf_counter() if self.recorder is not None else None
            lr = self.optimizer.get_lr() if lr is None else lr
            with span("trainer.place_batch", step=step):
                batch, sig, batch_sh = self.place_batch(batch)
            step_fn = self._placed_step(sig, batch_sh)
            with span("trainer.dispatch", step=step):
                (self.params, self.opt_state, self.gt_state, self.consts,
                 loss) = step_fn(
                    self.params, self.opt_state, self.gt_state,
                    self.consts, lr, batch)
            sched = self.optimizer._lr_scheduler
            if sched is not None:
                sched.step()
            self._host_step += 1
            if self.recorder is not None:
                self._record_tick(("step", 1), 1, t0)
            return loss

    def sync_to_model(self):
        """Copy trained params AND accumulated buffers (BN running stats)
        back into the Layer tree (for save/eval)."""
        load_state_pytree(self.model, {**self.consts, **self.params})

    def state(self):
        """Host-side snapshot (numpy leaves). Device buffers are donated
        into the next step(), so a live-array snapshot would be invalidated
        the moment training continues."""
        s = {"params": self.params, "opt_state": self.opt_state,
             "step": self._host_step}
        if self.gt_state is not None:   # grad-transform residuals (DGC u/v)
            s["gt_state"] = self.gt_state
        return jax.tree_util.tree_map(
            lambda v: jax.device_get(v) if hasattr(v, "dtype") else v, s)

    def load_state(self, state):
        # EVERY restored leaf is device_put onto the current trainer's
        # template sharding — params AND opt/grad-transform state. The
        # old code handed opt_state to the compiled step as raw numpy:
        # wrong placement semantics under a resharded mesh, and feeding
        # numpy into a DONATED argument of a deserialized (persistent-
        # cache-hit) executable mis-executes outright — silently wrong
        # resume losses, then heap corruption (the
        # tests/test_cross_mesh_resume.py crash that killed whole suite
        # runs).
        def put(t, v):
            if not hasattr(v, "dtype"):
                return v
            sh = getattr(t, "sharding", None)
            if sh is not None and getattr(sh, "num_devices", 1) > 1:
                return jax.device_put(v, sh)
            # template leaf is default-placed (eager opt-state init):
            # an uncommitted device array lets dispatch place it, while
            # still never handing raw HOST memory to a donated argument
            return jnp.asarray(v)

        def put_tree(template, tree):
            return jax.tree_util.tree_map(put, template, tree)

        self.params = put_tree(self.params, state["params"])
        self.opt_state = put_tree(self.opt_state, state["opt_state"])
        if "gt_state" in state:
            self.gt_state = put_tree(self.gt_state, state["gt_state"])
        self._host_step = int(state.get("step", 0))
        # restored leaves may carry different shardings (resharded mesh,
        # default-placed opt state): drop the specialized steps so the next
        # step()/step_multi() re-derives in_shardings from the actual arrays
        self._placed_steps = {}
        self._placed_multis = {}
