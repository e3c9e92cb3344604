"""The plain reference's training steps, layer by layer and in blocks of
rows, so that a model whose step fills the chip can be followed beside its
own gradients: one stage's activations and one stage's gradient are alive at
a time, and the gradient of the whole batch is accumulated in float32.

It follows the recipe the cell's file states and nothing of the program:
global-norm clipping, then AdamW with decoupled decay on every leaf, moments
and parameters stored in the stated types (rounded to nearest on store),
all arithmetic in float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np


class Reference:
    """`model` is a module of this directory (gpt, bert); `cfg` its
    configuration dict; `opt` the cell's optimizer block."""

    def __init__(self, model, cfg, opt, seed, prec="f32", row_block=2):
        self.model, self.cfg, self.opt, self.prec = model, cfg, opt, prec
        self.row_block = row_block
        self.params = model.init_params(cfg, seed)
        mdt = jnp.dtype(opt["moment_dtype"])
        self.m = {k: jnp.zeros(v.shape, mdt) for k, v in self.params.items()}
        self.v = {k: jnp.zeros(v.shape, mdt) for k, v in self.params.items()}
        self.step_no = 0
        self._jits = {}
        self.stages = model.stages(cfg)
        self.leaves = {s: model.stage_leaves(cfg, s) for s in self.stages}

    # ------------------------------------------------------- compiled bits
    def _jit(self, key, make):
        if key not in self._jits:
            self._jits[key] = make()
        return self._jits[key]

    def _fwd(self, stage):
        fn, key = self.model.stage_fn(self.cfg, stage)
        prec = self.prec
        return self._jit(("fwd", key), lambda: jax.jit(
            lambda p, x, rows: fn(p, x, rows, prec)))

    def _bwd(self, stage):
        fn, key = self.model.stage_fn(self.cfg, stage)
        prec = self.prec

        def bwd(p, x, rows, dy):
            _, vjp = jax.vjp(lambda p_, x_: fn(p_, x_, rows, prec), p, x)
            return vjp(dy)

        def bwd_first(p, rows, dy):
            _, vjp = jax.vjp(lambda p_: fn(p_, None, rows, prec), p)
            return vjp(dy)[0]

        first = stage == self.stages[0]
        return self._jit(("bwd", key, first), lambda: jax.jit(
            bwd_first if first else bwd))

    def _head(self):
        fn, key = self.model.stage_fn(self.cfg, self.stages[-1])
        prec = self.prec
        return self._jit(("head", key), lambda: jax.jit(
            lambda p, x, rows, denom: jax.value_and_grad(
                lambda p_, x_: fn(p_, x_, rows, prec, denom),
                argnums=(0, 1))(p, x)))

    def _stage_params(self, stage):
        return {loc: self.params[flat]
                for loc, flat in self.leaves[stage].items()}

    # ------------------------------------------------------------- a step
    def grads(self, batch):
        """(loss, float32 gradient of the batch's mean loss by flat name)"""
        add = self._jit("add", lambda: jax.jit(
            lambda a, b: a + b.astype(jnp.float32), donate_argnums=0))
        acc = {}

        def accumulate(stage, g):
            for loc, flat in self.leaves[stage].items():
                acc[flat] = add(acc[flat], g[loc]) if flat in acc \
                    else g[loc].astype(jnp.float32)

        denom = jnp.asarray(self.model.count_labels(batch), jnp.float32)
        n = len(next(iter(batch.values())))
        loss = 0.0
        for lo in range(0, n, self.row_block):
            rows = {k: jnp.asarray(v[lo:lo + self.row_block])
                    for k, v in batch.items()}
            xs = [None]
            for s in self.stages[:-1]:
                xs.append(self._fwd(s)(self._stage_params(s), xs[-1], rows))
            head = self.stages[-1]
            part, (g, dx) = self._head()(self._stage_params(head), xs[-1],
                                         rows, denom)
            loss += float(part)
            accumulate(head, g)
            for i in range(len(self.stages) - 2, 0, -1):
                s = self.stages[i]
                g, dx = self._bwd(s)(self._stage_params(s), xs[i], rows, dx)
                accumulate(s, g)
                xs[i + 1] = None
            s = self.stages[0]
            accumulate(s, self._bwd(s)(self._stage_params(s), rows, dx))
        return loss, acc

    def _clip_scale(self, grads):
        sq = self._jit("sq", lambda: jax.jit(
            lambda g: jnp.sum(jnp.square(g))))
        total = float(np.sqrt(sum(float(sq(g)) for g in grads.values())))
        clip = self.opt.get("clip_global_norm")
        return 1.0 if not clip else min(clip / max(total, 1e-12), 1.0)

    def step(self, batch):
        """One optimizer step. Returns (loss, {leaf: norm of the clipped
        gradient})."""
        o = self.opt
        loss, grads = self.grads(batch)
        scale = self._clip_scale(grads)
        self.step_no += 1
        b1, b2, eps, lr, wd = (o["beta1"], o["beta2"], o["epsilon"],
                               o["learning_rate"], o["weight_decay"])

        def update(p, m, v, g, scale, t):
            g = g * scale
            p32 = p.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
            mhat = m32 / (1 - b1 ** t)
            vhat = v32 / (1 - b2 ** t)
            new = p32 - lr * mhat / (jnp.sqrt(vhat) + eps) - lr * wd * p32
            return (new.astype(p.dtype), m32.astype(m.dtype),
                    v32.astype(v.dtype))

        upd = self._jit("update", lambda: jax.jit(
            update, donate_argnums=(0, 1, 2)))
        t = jnp.float32(self.step_no)
        norms = {k: scale * v
                 for k, v in part_norms(grads, self.model.leaf_parts).items()}
        for k in list(grads):
            self.params[k], self.m[k], self.v[k] = upd(
                self.params[k], self.m[k], self.v[k], grads.pop(k),
                jnp.float32(scale), t)
        return loss, norms

    def change_norms(self, seed):
        """{leaf: norm of (parameters now - parameters at the start)}"""
        start = self.model.init_params(self.cfg, seed)
        return change_norms(self.params, start, self.model.leaf_parts)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _part_norm(a, b, axis, parts):
    """Norms of the `parts` equal slices of (a - b) along `axis`."""
    d = a.astype(jnp.float32) - (0.0 if b is None else b.astype(jnp.float32))
    d = jnp.moveaxis(d, axis, 0).reshape(parts, -1)
    return jnp.sqrt(jnp.sum(jnp.square(d), axis=1))


def part_norms(tree, leaf_parts, minus=None):
    """{leaf, or leaf#i for a leaf that fuses several (q, k and v in one):
    norm}. `leaf_parts(name)` gives (axis, parts) for such a leaf. Each part
    is compared on its own: a key's bias has no gradient under softmax, and
    fused with q's and v's it would hide in their norm."""
    out = {}
    for k, v in tree.items():
        axis, parts = leaf_parts(k)
        got = np.asarray(_part_norm(v, None if minus is None else minus[k],
                                    axis, parts))
        if parts == 1:
            out[k] = float(got[0])
        else:
            out.update({f"{k}#{i}": float(x) for i, x in enumerate(got)})
    return out


def change_norms(now, start, leaf_parts):
    return part_norms(now, leaf_parts, minus=start)


def follow(model, cfg, opt, seed, batches, prec="f32", row_block=2):
    """Drive a fresh reference through `batches`. Returns what a training
    cell compares: each step's loss, the first clipped gradient's norm by
    leaf, and each leaf's change over the steps."""
    ref = Reference(model, cfg, opt, seed, prec=prec, row_block=row_block)
    losses, first = [], None
    for b in batches:
        loss, norms = ref.step(b)
        losses.append(loss)
        first = first if first is not None else norms
    return {"losses": losses, "grad_norms": first,
            "change_norms": ref.change_norms(seed)}
