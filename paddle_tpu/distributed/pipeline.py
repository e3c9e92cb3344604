"""Pipeline parallelism: SPMD schedules over the 'pp' mesh axis.

Replaces reference fleet pipeline_parallel.py (P2P send/recv between rank
processes, GPipe/1F1B schedulers in python —
python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:82,171)
with the TPU-native formulation: ONE compiled program in which every stage
runs the same code, activations hop stages via ppermute on ICI, and the
microbatch schedule is a lax.scan over ticks. shard_map is manual ONLY over
'pp' (axis_names={'pp'}) so tensor/data parallel dims inside each stage stay
GSPMD-managed — pp×tp×dp×sp compose.

Three schedules:

- "gpipe": forward scan, backward by XLA autodiff of the scan. Simple, but
  the autodiff saves EVERY tick's stage residuals (all internal
  activations × (M+S-1) ticks) for the backward — the GPipe liveness
  profile.
- "1f1b": custom_vjp. Forward saves only each tick's stage INPUT (one
  microbatch activation per tick); backward is an explicit reverse scan
  that recomputes the stage forward and runs its VJP, with activation
  gradients hopping backward over the reverse ppermute ring. This is the
  1F1B memory discipline (peak extra liveness = per-tick inputs, not full
  residuals) expressed as a single XLA program. Measured on GPTStacked
  pp=4×dp=2, 8 microbatches (examples/bench_pipeline.py): 1.56× faster
  and 5.7× less temp memory than "gpipe".
- "interleaved": virtual pipeline stages (reference
  fleet/meta_parallel/pipeline_parallel.py interleaved 1F1B scheduler +
  Megatron-LM interleaving). Each device owns `virtual` non-contiguous
  layer chunks; chunk c on device d is global virtual stage c*S+d, so one
  microbatch visits every device V times. A tick does 1/V of a stage's
  work, shrinking the pipeline-fill bubble from (S-1) stage-ticks to
  ~(S-1) CHUNK-ticks — the bubble fraction drops by the virtual factor V.
  The schedule itself is simulated on the host at trace time (greedy
  earliest-ready, breadth-first priority) and baked into the compiled
  program as static gather tables; activations hop on a forward ppermute
  ring plus a wrap ring (last device → device 0) between chunks.
- "interleaved_1f1b": the interleaved schedule with the 1F1B recompute
  backward (reference interleaved-1F1B,
  fleet/meta_parallel/pipeline_parallel.py:171): virtual-stage bubble AND
  per-tick-input liveness. Measured on GPTStacked pp=4×dp=2, 8
  microbatches (examples/bench_pipeline.py): 1.19× faster and 8.3× less
  temp memory than "interleaved"'s autodiff backward.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "interleaved_schedule_table"]

def _make_varying(axis_name):
    def _varying(z):
        try:
            return jax.lax.pcast(z, (axis_name,), to="varying")
        except ValueError:       # already varying over axis_name
            return z
    return _varying


def _make_fwd_scan(stage_fn, n_micro, n_stages, axis_name):
    """Shared forward schedule. Returns (out, per-tick stage inputs)."""
    M, S = n_micro, n_stages
    T = M + S - 1
    perm = [(i, i + 1) for i in range(S - 1)]
    _varying = _make_varying(axis_name)

    def fwd_scan(params_local, xv):
        idx = jax.lax.axis_index(axis_name)
        B = xv.shape[0]
        mb = xv.reshape((M, B // M) + xv.shape[1:])
        out_buf0 = _varying(jnp.zeros_like(mb))
        recv0 = _varying(jnp.zeros_like(mb[0]))

        def tick(carry, t):
            out_buf, recv = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            x_t = jax.lax.dynamic_index_in_dim(mb, mb_idx, 0, keepdims=False)
            x_in = jnp.where(idx == 0, x_t, recv)
            y = stage_fn(params_local, x_in)
            widx = jnp.clip(t - (S - 1), 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(out_buf, widx, 0, keepdims=False)
            write = jnp.where(t >= S - 1, y, cur)
            out_buf = jax.lax.dynamic_update_index_in_dim(out_buf, write, widx, 0)
            recv = jax.lax.ppermute(y, axis_name, perm)
            return (out_buf, recv), x_in

        (out_buf, _), xs = jax.lax.scan(tick, (out_buf0, recv0), jnp.arange(T))
        # only the LAST stage's buffer holds the model output; psum-broadcast
        out_buf = jnp.where(idx == S - 1, out_buf, jnp.zeros_like(out_buf))
        out_buf = jax.lax.psum(out_buf, axis_name)
        return out_buf.reshape(xv.shape[:1] + out_buf.shape[2:]), xs

    return fwd_scan, _varying


def _gpipe_local(stage_fn, n_micro, n_stages, axis_name):
    fwd_scan, _ = _make_fwd_scan(stage_fn, n_micro, n_stages, axis_name)
    return lambda params_local, xv: fwd_scan(params_local, xv)[0]


def _1f1b_local(stage_fn, n_micro, n_stages, axis_name):
    """1F1B-liveness schedule as a custom_vjp over the local (per-stage)
    computation. Same tick count as GPipe (the pipeline bubble is
    fundamental); the difference is what the backward reads: saved stage
    inputs + recompute, never the full per-tick residual stash."""
    M, S = n_micro, n_stages
    T = M + S - 1
    rev_perm = [(i + 1, i) for i in range(S - 1)]
    fwd_scan, _varying = _make_fwd_scan(stage_fn, M, S, axis_name)

    @jax.custom_vjp
    def run(params_local, xv):
        out, _ = fwd_scan(params_local, xv)
        return out

    def run_fwd(params_local, xv):
        out, xs = fwd_scan(params_local, xv)
        return out, (params_local, xs)

    def run_bwd(res, g):
        params_local, xs = res
        idx = jax.lax.axis_index(axis_name)
        mb_shape = xs.shape[1:]          # one microbatch of activations
        gmb = g.reshape((M,) + mb_shape[:1] + g.shape[1:])
        zero_mb = _varying(jnp.zeros_like(xs[0]))
        dparams0 = jax.tree_util.tree_map(
            lambda v: _varying(jnp.zeros_like(v)), params_local)
        dmb0 = _varying(jnp.zeros((M,) + mb_shape, xs.dtype))

        def btick(carry, r):
            dparams, dmb, dsend = carry
            t = T - 1 - r
            grad_recv = jax.lax.ppermute(dsend, axis_name, rev_perm)
            # cotangent of this stage's tick-t output
            widx = jnp.clip(t - (S - 1), 0, M - 1)
            g_t = jax.lax.dynamic_index_in_dim(gmb, widx, 0, keepdims=False)
            dy_last = jnp.where(t >= S - 1, g_t.astype(xs.dtype),
                                jnp.zeros_like(g_t, xs.dtype))
            dy = jnp.where(idx == S - 1, dy_last, grad_recv)
            # ticks where this stage processed garbage contribute nothing
            valid = jnp.logical_and(t - idx >= 0, t - idx <= M - 1)
            dy = jnp.where(valid, dy, jnp.zeros_like(dy))
            x_in = jax.lax.dynamic_index_in_dim(xs, t, 0, keepdims=False)
            _, vjp_fn = jax.vjp(stage_fn, params_local, x_in)
            dp_t, dx_t = vjp_fn(dy)
            dparams = jax.tree_util.tree_map(jnp.add, dparams, dp_t)
            # stage 0's input grad is the pipeline input's microbatch grad
            mb_idx = jnp.clip(t, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(dmb, mb_idx, 0, keepdims=False)
            upd = jnp.where(jnp.logical_and(idx == 0, valid), dx_t, cur)
            dmb = jax.lax.dynamic_update_index_in_dim(dmb, upd, mb_idx, 0)
            return (dparams, dmb, dx_t), None

        (dparams, dmb, _), _ = jax.lax.scan(
            btick, (dparams0, dmb0, zero_mb), jnp.arange(T))
        dxv = dmb.reshape((M * mb_shape[0],) + mb_shape[1:])
        # only stage 0 holds the true input grad; psum the masked value so
        # the cotangent is pp-invariant, matching the replicated in_spec
        dxv = jnp.where(idx == 0, dxv, jnp.zeros_like(dxv))
        return dparams, jax.lax.psum(dxv, axis_name)

    run.defvjp(run_fwd, run_bwd)
    return run


def _simulate_interleaved(n_micro, n_stages, virtual):
    """Greedy earliest-ready simulation of the interleaved schedule.

    Work item (m, k): microbatch m at global virtual stage k = c*S + d
    (chunk c of device d). Item input is ready one tick after the previous
    virtual stage computed it; each device runs at most one chunk per tick;
    ties broken breadth-first (lowest chunk, then lowest microbatch), which
    keeps the wrap link busy and realizes the ~(S-1)-chunk-tick fill bubble.

    Returns (T, compute) with compute = [(t, d, m, c), ...].
    """
    M, S, V = n_micro, n_stages, virtual
    SV = S * V
    avail = {(m, 0): 0 for m in range(M)}       # (m, k) -> ready tick
    done = set()
    compute = []                                # (t, d, m, c)
    t = 0
    while len(done) < M * SV:
        for d in range(S):
            ready = [(c, m)
                     for c in range(V) for m in range(M)
                     if (m, c * S + d) not in done
                     and avail.get((m, c * S + d), None) is not None
                     and avail[(m, c * S + d)] <= t]
            if not ready:
                continue
            c, m = min(ready)
            k = c * S + d
            done.add((m, k))
            compute.append((t, d, m, c))
            if k + 1 < SV:
                avail[(m, k + 1)] = t + 1
        t += 1
    return t, compute


def interleaved_schedule_table(n_micro, n_stages, virtual):
    """Forward tables, dict of numpy [T, S]:
      work/mb/ch    — does device d compute at tick t, and which (m, c)
      stv/stm/stc   — should device d STORE the value received at tick t,
                      and into which buffer slot (m, c)
      out           — is this tick's computed y a final-stage output
    """
    M, S, V = n_micro, n_stages, virtual
    SV = S * V
    T, compute = _simulate_interleaved(M, S, V)
    tbl = {key: np.zeros((T, S), np.int32)
           for key in ("work", "mb", "ch", "stv", "stm", "stc", "out")}
    for (tc, d, m, c) in compute:
        k = c * S + d
        tbl["work"][tc, d] = 1
        tbl["mb"][tc, d] = m
        tbl["ch"][tc, d] = c
        if k == SV - 1:
            tbl["out"][tc, d] = 1
        elif tc + 1 < T:
            d2 = (k + 1) % S
            tbl["stv"][tc + 1, d2] = 1
            tbl["stm"][tc + 1, d2] = m
            tbl["stc"][tc + 1, d2] = (k + 1) // S
    return T, tbl


def interleaved_backward_tables(n_micro, n_stages, virtual):
    """Mirror tables for the 1F1B recompute backward: device d re-runs the
    VJP of exactly the items it computed forward, at mirrored ticks
    r = T-1-t.  The consumer of item (m,k)'s output is item (m,k+1) on
    device (k+1)%S at forward tick t2 > t; its input-cotangent dx hops the
    REVERSE ring at backward tick r2 = T-1-t2 and is stored by d one tick
    later (r2+1 <= r, so it is always buffered before use).
    """
    M, S, V = n_micro, n_stages, virtual
    SV = S * V
    T, compute = _simulate_interleaved(M, S, V)
    item_tick = {(m, c * S + d): t for (t, d, m, c) in compute}
    tbl = {key: np.zeros((T, S), np.int32)
           for key in ("work", "mb", "ch", "stv", "stm", "stc", "out")}
    for (tc, d, m, c) in compute:
        k = c * S + d
        r = T - 1 - tc
        tbl["work"][r, d] = 1
        tbl["mb"][r, d] = m
        tbl["ch"][r, d] = c
        if k == SV - 1:
            tbl["out"][r, d] = 1        # dy comes straight from g[m]
        else:
            r2 = T - 1 - item_tick[(m, k + 1)]
            tbl["stv"][r2 + 1, d] = 1
            tbl["stm"][r2 + 1, d] = m
            tbl["stc"][r2 + 1, d] = c
    return T, tbl


def _make_interleaved_fwd(stage_fn, n_micro, n_stages, virtual, axis_name):
    """Shared interleaved forward scan. Returns (out, per-tick chunk
    inputs xs [T, ...]) — xs is the only residual the 1F1B backward
    needs. params_local leaves are [V*cl, ...]: chunk c of THIS device =
    rows [c*cl, (c+1)*cl) after the interleave permutation applied in
    pipeline_apply."""
    M, S, V = n_micro, n_stages, virtual
    T, tbl = interleaved_schedule_table(M, S, V)
    jt = {k: jnp.asarray(v) for k, v in tbl.items()}
    # one full-ring hop per tick: d -> d+1, plus the S-1 -> 0 wrap that
    # carries chunk c outputs into chunk c+1 on device 0
    perm_ring = [(i, (i + 1) % S) for i in range(S)]
    _varying = _make_varying(axis_name)

    def fwd_scan(params_local, xv):
        idx = jax.lax.axis_index(axis_name)
        B = xv.shape[0]
        mb = xv.reshape((M, B // M) + xv.shape[1:])
        mb_shape = mb.shape[1:]
        cl = jax.tree_util.tree_leaves(params_local)[0].shape[0] // V
        buf0 = _varying(jnp.zeros((V, M) + mb_shape, xv.dtype))
        out0 = _varying(jnp.zeros_like(mb))
        ysend0 = _varying(jnp.zeros(mb_shape, xv.dtype))
        zero_nd = (0,) * len(mb_shape)

        def tick(carry, t):
            buf, out_buf, ysend = carry
            # 1) receive last tick's hop on the ring
            recv = jax.lax.ppermute(ysend, axis_name, perm_ring)
            stv, stm, stc = jt["stv"][t, idx], jt["stm"][t, idx], jt["stc"][t, idx]
            cur = jax.lax.dynamic_slice(buf, (stc, stm) + zero_nd,
                                        (1, 1) + mb_shape)[0, 0]
            buf = jax.lax.dynamic_update_slice(
                buf, jnp.where(stv == 1, recv, cur)[None, None],
                (stc, stm) + zero_nd)
            # 2) compute this tick's chunk (idle devices run on garbage;
            #    consumers are gated by the tables so it never escapes)
            w, m, c = jt["work"][t, idx], jt["mb"][t, idx], jt["ch"][t, idx]
            x_direct = jax.lax.dynamic_index_in_dim(mb, m, 0, keepdims=False)
            x_buf = jax.lax.dynamic_slice(buf, (c, m) + zero_nd,
                                          (1, 1) + mb_shape)[0, 0]
            x_in = jnp.where(jnp.logical_and(idx == 0, c == 0), x_direct, x_buf)
            p_c = jax.tree_util.tree_map(
                lambda v: jax.lax.dynamic_slice_in_dim(v, c * cl, cl, 0),
                params_local)
            y = stage_fn(p_c, x_in)
            # 3) final-virtual-stage outputs land in the output buffer
            out_cur = jax.lax.dynamic_index_in_dim(out_buf, m, 0, keepdims=False)
            is_out = jnp.logical_and(w == 1, jt["out"][t, idx] == 1)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(is_out, y, out_cur), m, 0)
            return (buf, out_buf, y), x_in

        (_, out_buf, _), xs = jax.lax.scan(tick, (buf0, out0, ysend0),
                                           jnp.arange(T))
        # final virtual stage SV-1 lives on device S-1
        out_buf = jnp.where(idx == S - 1, out_buf, jnp.zeros_like(out_buf))
        out_buf = jax.lax.psum(out_buf, axis_name)
        return out_buf.reshape(xv.shape[:1] + out_buf.shape[2:]), xs

    return fwd_scan, _varying


def _interleaved_local(stage_fn, n_micro, n_stages, virtual, axis_name):
    """Interleaved forward, backward by XLA autodiff of the scan (GPipe
    liveness: the autodiff saves every tick's internal stage residuals)."""
    fwd_scan, _ = _make_interleaved_fwd(stage_fn, n_micro, n_stages,
                                        virtual, axis_name)
    return lambda params_local, xv: fwd_scan(params_local, xv)[0]


def _interleaved_1f1b_local(stage_fn, n_micro, n_stages, virtual, axis_name):
    """Interleaved schedule WITH the 1F1B recompute backward (reference
    fleet/meta_parallel/pipeline_parallel.py:171 — interleaved 1F1B):
    forward saves only each tick's chunk input; the backward replays the
    mirrored schedule, recomputing each chunk forward and applying its
    VJP, with input-cotangents hopping the reverse ring and buffering in
    a [V, M] grad buffer until their producer's backward tick."""
    M, S, V = n_micro, n_stages, virtual
    SV = S * V
    T, btbl = interleaved_backward_tables(M, S, V)
    jb = {k: jnp.asarray(v) for k, v in btbl.items()}
    rev_ring = [((i + 1) % S, i) for i in range(S)]
    fwd_scan, _varying = _make_interleaved_fwd(stage_fn, M, S, V, axis_name)

    @jax.custom_vjp
    def run(params_local, xv):
        return fwd_scan(params_local, xv)[0]

    def run_fwd(params_local, xv):
        out, xs = fwd_scan(params_local, xv)
        return out, (params_local, xs)

    def run_bwd(res, g):
        params_local, xs = res
        idx = jax.lax.axis_index(axis_name)
        mb_shape = xs.shape[1:]
        cl = jax.tree_util.tree_leaves(params_local)[0].shape[0] // V
        gmb = g.reshape((M,) + mb_shape[:1] + g.shape[1:]).astype(xs.dtype)
        zero_nd = (0,) * len(mb_shape)
        dbuf0 = _varying(jnp.zeros((V, M) + mb_shape, xs.dtype))
        dmb0 = _varying(jnp.zeros((M,) + mb_shape, xs.dtype))
        dsend0 = _varying(jnp.zeros(mb_shape, xs.dtype))
        dparams0 = jax.tree_util.tree_map(
            lambda v: _varying(jnp.zeros_like(v)), params_local)

        def btick(carry, r):
            dbuf, dmb, dparams, dsend = carry
            # 1) receive the reverse-ring hop, store per mirror tables
            drecv = jax.lax.ppermute(dsend, axis_name, rev_ring)
            stv, stm, stc = jb["stv"][r, idx], jb["stm"][r, idx], jb["stc"][r, idx]
            cur = jax.lax.dynamic_slice(dbuf, (stc, stm) + zero_nd,
                                        (1, 1) + mb_shape)[0, 0]
            dbuf = jax.lax.dynamic_update_slice(
                dbuf, jnp.where(stv == 1, drecv, cur)[None, None],
                (stc, stm) + zero_nd)
            # 2) backward-compute this tick's mirrored item
            w, m, c = jb["work"][r, idx], jb["mb"][r, idx], jb["ch"][r, idx]
            is_out = jb["out"][r, idx]
            g_t = jax.lax.dynamic_index_in_dim(gmb, m, 0, keepdims=False)
            d_buf = jax.lax.dynamic_slice(dbuf, (c, m) + zero_nd,
                                          (1, 1) + mb_shape)[0, 0]
            dy = jnp.where(is_out == 1, g_t, d_buf)
            dy = jnp.where(w == 1, dy, jnp.zeros_like(dy))
            t = T - 1 - r
            x_in = jax.lax.dynamic_index_in_dim(xs, t, 0, keepdims=False)
            p_c = jax.tree_util.tree_map(
                lambda v: jax.lax.dynamic_slice_in_dim(v, c * cl, cl, 0),
                params_local)
            _, vjp_fn = jax.vjp(stage_fn, p_c, x_in)
            dp_t, dx_t = vjp_fn(dy)
            dparams = jax.tree_util.tree_map(
                lambda acc, dpc: jax.lax.dynamic_update_slice_in_dim(
                    acc,
                    jax.lax.dynamic_slice_in_dim(acc, c * cl, cl, 0) + dpc,
                    c * cl, 0),
                dparams, dp_t)
            # 3) global-first-stage items feed the input cotangent
            is_first = jnp.logical_and(jnp.logical_and(idx == 0, c == 0),
                                       w == 1)
            cur_dmb = jax.lax.dynamic_index_in_dim(dmb, m, 0, keepdims=False)
            dmb = jax.lax.dynamic_update_index_in_dim(
                dmb, jnp.where(is_first, dx_t, cur_dmb), m, 0)
            return (dbuf, dmb, dparams, dx_t), None

        (_, dmb, dparams, _), _ = jax.lax.scan(
            btick, (dbuf0, dmb0, dparams0, dsend0), jnp.arange(T))
        dxv = dmb.reshape((M * mb_shape[0],) + mb_shape[1:])
        dxv = jnp.where(idx == 0, dxv, jnp.zeros_like(dxv))
        return dparams, jax.lax.psum(dxv, axis_name)

    run.defvjp(run_fwd, run_bwd)
    return run


def _interleave_perm(n_layers, n_stages, virtual):
    """Permutation mapping contiguous [L] layers to the interleaved
    device-major layout: device d holds (in order) the layers of virtual
    stages d, S+d, 2S+d, … so a plain 'pp'-sharding of dim 0 gives each
    device its V chunks contiguously."""
    cl = n_layers // (n_stages * virtual)
    perm = []
    for d in range(n_stages):
        for c in range(virtual):
            v = c * n_stages + d
            perm.extend(range(v * cl, (v + 1) * cl))
    return np.asarray(perm, np.int32)


def pipeline_apply(stage_fn, stacked_params, x, n_microbatch, mesh=None,
                   axis_name="pp", param_specs=None, schedule="gpipe",
                   virtual=2, pre_permuted=False):
    """Run layers stacked on leading dim through a pipeline schedule.

    stage_fn(local_params, x) -> y   applies this stage's layer slice
    stacked_params: pytree, leaves [L_total, ...], sharded over 'pp' on dim 0
    x: [B, ...] activations (replicated w.r.t. 'pp')
    schedule: "gpipe" (autodiff backward), "1f1b" (recompute backward
              with 1F1B activation liveness), or "interleaved" (virtual
              pipeline stages — `virtual` chunks per device)
    virtual: chunks per device for schedule="interleaved"
    pre_permuted: the caller already stores stacked_params in the
              interleaved device-major layout (_interleave_perm), so the
              compiled step does zero layer resharding. When False the
              permutation happens here via jnp.take — correct, but it
              costs an all-to-all of the whole layer stack every step;
              long-lived models should permute their storage once instead
              (see GPTStacked).
    """
    from .mesh import get_mesh

    mesh = mesh or get_mesh()
    n_stages = mesh.shape.get(axis_name, 1)
    if n_stages == 1:
        return stage_fn(stacked_params, x)

    n_micro = n_microbatch
    assert x.shape[0] % n_micro == 0, "batch must divide microbatches"

    if schedule == "1f1b":
        local_fn = _1f1b_local(stage_fn, n_micro, n_stages, axis_name)
    elif schedule == "gpipe":
        local_fn = _gpipe_local(stage_fn, n_micro, n_stages, axis_name)
    elif schedule in ("interleaved", "interleaved_1f1b"):
        L_total = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        if virtual <= 1 or L_total % (n_stages * virtual):
            raise ValueError(
                f"interleaved schedule needs layers ({L_total}) divisible by "
                f"pp*virtual ({n_stages}*{virtual}) and virtual>1")
        if not pre_permuted:
            perm = jnp.asarray(_interleave_perm(L_total, n_stages, virtual))
            stacked_params = jax.tree_util.tree_map(
                lambda v: jnp.take(v, perm, axis=0), stacked_params)
        make = (_interleaved_1f1b_local if schedule == "interleaved_1f1b"
                else _interleaved_local)
        local_fn = make(stage_fn, n_micro, n_stages, virtual, axis_name)
    else:
        raise ValueError(f"unknown pipeline schedule {schedule!r} (want "
                         "'gpipe', '1f1b', 'interleaved' or "
                         "'interleaved_1f1b')")

    if param_specs is None:
        param_specs = jax.tree_util.tree_map(
            lambda v: P(axis_name, *([None] * (v.ndim - 1))), stacked_params)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        axis_names={axis_name})(stacked_params, x)
