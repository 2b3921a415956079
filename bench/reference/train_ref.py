"""The reference of a sparse training job: the same weights, rows and
random block selections as the benchmark gives the program, followed
through the first steps in float32 (or in the fp8 control mode).

Per step: the forward of the frozen prefix layer by layer, through every
segment of the program in order; the loss of the trainable suffix (the
last `update_layers` layers of the last segment) and the head, its
gradient at the selected channel blocks of every selectable weight (one
block draw per layer, shared by all experts of an expert weight) and at
every other leaf of the suffix, and the optimizer rule on exactly those
values; the weights are stored back in their configured dtype. The
embedding, head and final norm stay frozen.

A reference module gives `leaf_specs`, `selectable_leaves`, `embed`,
`layer`, `head_weight` and `flops_per_token`, and either `SEGMENT` (one
segment of all `num_layers` layers) or `segments(m)`, the program's
segments in order as [(name, layers)], with `LAYERS`, the layer function
of each segment by name. A module whose loss has terms over the whole
batch (a router's balance loss) gives `aux_loss(m, sums, tokens)`: its
layer functions then return (x, sums), each a sum over the row's tokens,
and the loss is the rows' mean cross-entropy plus `aux_loss` of every
layer's sums over the whole batch. The rows go one at a time, so the
gradient takes `aux_loss` linearised at the batch's sums: exact, since
its gradient is a sum over rows of each row's sums' Jacobian times the
same cotangent.

Readings, keyed by the leaf's path under the segment ("time/wr"):
  losses        the loss of each step;
  grad_norms    per leaf, the norm of the first step's gradient as the
                optimizer holds it after that step (AdamW: mu / (1 - beta1);
                SGD: (w0 - w1) / lr), over the whole leaf;
  change_norms  per leaf, the norm of w_n - w_0 after the n steps.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import common as C


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_params(ref, m):
    """The benchmark's weights, made on the device in one jitted call from
    the key, in the program's layout and dtypes."""
    specs = ref.leaf_specs(m)

    def init(key):
        out = {}
        for i, path in enumerate(sorted(specs)):
            shape, dtype, how = specs[path]
            k = jax.random.fold_in(key, i)
            if how[0] == "normal":
                val = jax.random.normal(k, shape, dtype) * jnp.asarray(
                    how[1], dtype)
            elif how[0] == "uniform":
                val = jax.random.uniform(k, shape, dtype, how[1], how[2])
            else:
                val = jnp.full(shape, how[1], dtype)
            _set(out, path, val)
        return out

    return init


def segments(ref, m) -> list:
    """[(name, layers)] of the program's segments in order."""
    if hasattr(ref, "segments"):
        return ref.segments(m)
    return [(ref.SEGMENT, m["num_layers"])]


def segment_layer(ref, name):
    """The module's layer function for the segment `name`."""
    return getattr(ref, "LAYERS", {}).get(name, getattr(ref, "layer", None))


def suffix_segment(ref, m, k: int) -> tuple:
    """(name, index of the first trainable layer) of the last segment, which
    holds the trainable suffix: its last k layers."""
    name, n = segments(ref, m)[-1]
    if n < k:
        raise SystemExit(f"bench: {k} trainable layers asked for, but the "
                         f"last segment {name!r} holds {n}; the reference "
                         f"trains within the last segment only")
    return name, n - k


def seg_paths(ref, m):
    """Leaf paths within one layer of the last segment, sorted."""
    seg = ("segments", segments(ref, m)[-1][0])
    n = len(seg)
    return sorted(p[n:] for p in ref.leaf_specs(m) if p[:n] == seg)


def key_of(path) -> str:
    return "/".join(path)


def layer_at(layer, m, blocks, i, x, mode):
    """Layer i of a segment's stacked weights applied to x."""
    get = lambda *p: jax.lax.dynamic_index_in_dim(_get(blocks, p), i,
                                                  keepdims=False)
    return layer(m, get, x, mode)


class Reference:
    """Follows the first steps of one sparse training job."""

    def __init__(self, ref, m, traffic, mode: str):
        self.ref, self.m, self.mode = ref, m, mode
        self.k = traffic["update_layers"]
        self.ratio = traffic["update_ratio"]
        self.block_req = traffic["channel_block"]
        self.opt = traffic["optimizer"]
        self.segs = segments(ref, m)
        self.seg, self.first = suffix_segment(ref, m, self.k)
        self.aux = hasattr(ref, "aux_loss")
        self.sel_leaves = ref.selectable_leaves(m)
        self.sel_paths = {leaf[0] for leaf in self.sel_leaves}
        self.blocks = {leaf[0]: C.sel_spec(leaf[2], self.ratio,
                                           self.block_req)[0]
                       for leaf in self.sel_leaves}
        self.paths = seg_paths(ref, m)
        self._embed = jax.jit(partial(ref.embed, mode=mode))
        self._layers = {name: jax.jit(partial(
            layer_at, segment_layer(ref, name), m, mode=mode))
            for name, _n in self.segs}
        self._grad = jax.jit(jax.value_and_grad(self._suffix_loss,
                                                argnums=(0, 1), has_aux=True))
        self._sums = jax.jit(lambda *a: self._suffix(*a)[1])
        self._update = jax.jit(self._apply, donate_argnums=(0, 1))

    # -- program pieces ----------------------------------------------------
    def _suffix(self, deltas, dense, train, sel, x):
        """The trainable layers on x: (their output, each layer's sums)."""
        layer = segment_layer(self.ref, self.seg)

        def one(raw, idx, dl, dn, x):
            def get(*p):
                if p in self.sel_paths:
                    return C.Sel(raw[p], idx[p], dl[p], self.blocks[p])
                return dn[p]
            return layer(self.m, get, x, self.mode)

        sums = []
        for j in range(self.k):
            pick = lambda t: {p: v[j] for p, v in t.items()}
            out = jax.checkpoint(one)(pick(train), pick(sel), pick(deltas),
                                      pick(dense), x)
            x, s = out if self.aux else (out, None)
            sums.append(s)
        return x, sums

    def _suffix_loss(self, deltas, dense, train, sel, x, labels, frozen,
                     g_sums):
        """(objective, cross-entropy) of one row: the objective adds the
        row's sums against g_sums, the cotangent of the batch's aux loss."""
        x, sums = self._suffix(deltas, dense, train, sel, x)
        h = C.norm(frozen["final_norm"], x)
        d = h.shape[-1]
        ce = C.mean_cross_entropy(h.reshape(-1, d), frozen["head"],
                                  labels.reshape(-1), self.mode)
        if g_sums is None:
            return ce, ce
        lin = sum(jnp.vdot(g, v) for g, v in zip(jax.tree.leaves(g_sums),
                                                 jax.tree.leaves(sums)))
        return ce + lin, ce

    def _apply(self, train, state, g_sel, g_dense, sel, t):
        """The optimizer rule on the selected blocks and the dense leaves;
        weights go back in their dtype, state in float32."""
        new_train, new_state = {}, {"mu": {}, "nu": {}}
        adam = self.opt["kind"] == "adamw"
        for p, w in train.items():
            mu = state["mu"].get(p)
            nu = state["nu"].get(p)
            if p in self.sel_paths:
                blk = self.blocks[p]
                rows_w, rows_mu, rows_nu = [], [], []
                for j in range(self.k):
                    idx = sel[p][j]
                    g = g_sel[p][j]
                    gat = lambda a: C.gather_blocks(a[j], idx, blk)
                    pn, mn, nn = C.rule(self.opt, t, gat(w), g,
                                        gat(mu) if adam else None,
                                        gat(nu) if adam else None)
                    rows_w.append(C.set_blocks(w[j], idx, pn, blk))
                    if adam:
                        rows_mu.append(C.set_blocks(mu[j], idx, mn, blk))
                        rows_nu.append(C.set_blocks(nu[j], idx, nn, blk))
                new_train[p] = jnp.stack(rows_w)
                if adam:
                    new_state["mu"][p] = jnp.stack(rows_mu)
                    new_state["nu"][p] = jnp.stack(rows_nu)
            else:
                pn, mn, nn = C.rule(self.opt, t, w, g_dense[p], mu, nu)
                new_train[p] = pn.astype(w.dtype)
                if adam:
                    new_state["mu"][p], new_state["nu"][p] = mn, nn
        return new_train, new_state

    # -- the steps -----------------------------------------------------------
    def run(self, params, batches, state_key, n_steps: int,
            rows: int | None = None) -> dict:
        """Follow `n_steps` steps on `batches` (host dicts of tokens and
        labels). `rows` keeps only the first rows of each batch (a planted
        fault: part of the batch left out)."""
        stacks = params["segments"]
        w0 = lambda: {p: _get(stacks[self.seg], p)[self.first:]
                      for p in self.paths}
        # a copy: the update donates it, and where the whole segment trains
        # the slice is the params' own array
        train = jax.tree.map(jnp.copy, w0())
        frozen = {"final_norm": params["final_norm"],
                  "head": self.ref.head_weight(params)}
        adam = self.opt["kind"] == "adamw"
        state = {"mu": {}, "nu": {}}
        if adam:
            state = {n: {p: jnp.zeros(v.shape, jnp.float32)
                         for p, v in train.items()} for n in ("mu", "nu")}
        out = {"losses": [], "grad_norms": {}, "change_norms": {}}
        for step in range(n_steps):
            tok = batches[step]["tokens"][:rows]
            lab = batches[step]["labels"][:rows]
            sel = C.draw_selection(state_key, step, self.seg,
                                   self.sel_leaves, self.k, self.ratio,
                                   self.block_req)
            deltas = {leaf[0]: jnp.zeros(self._delta_shape(
                leaf, sel[leaf[0]].shape[-1]), jnp.float32)
                for leaf in self.sel_leaves}
            dense = {p: v.astype(jnp.float32) for p, v in train.items()
                     if p not in self.sel_paths}
            # row by row, so that the activations of one row fit beside
            # the weights; every row has the same number of tokens, so the
            # batch's mean loss and gradient are the mean over rows
            n = tok.shape[0]
            xs, frozen_sums = [], None
            for r in range(n):
                x, sums = self._frozen(params, tok[r:r + 1])
                xs.append(x)
                if self.aux:
                    frozen_sums = sums if r == 0 else _add(frozen_sums, sums)
            g_sums, aux = None, 0.0
            if self.aux:
                g_sums, aux = self._aux(deltas, dense, train, sel, xs,
                                        frozen_sums, n * tok.shape[1])
            loss, grads = 0.0, None
            for r in range(n):
                (_, ce), g = self._grad(deltas, dense, train, sel, xs[r],
                                        jnp.asarray(lab[r:r + 1]), frozen,
                                        g_sums)
                loss += float(ce) / n
                grads = g if grads is None else _add(grads, g)
            g_sel, g_dense = _scale(grads, 1.0 / n)
            out["losses"].append(loss + aux)
            train, state = self._update(train, state, g_sel, g_dense, sel,
                                        jnp.float32(step + 1))
            if step == 0:
                out["grad_norms"] = first_grad_norms(self.opt, w0(), train,
                                                     state)
        out["change_norms"] = change_norms(w0(), train)
        return out

    def _delta_shape(self, leaf, n_sel: int) -> tuple:
        """[k(, experts), in, n_sel, block]: the offsets of a selectable
        leaf's selected blocks in the trainable layers."""
        e = C.leaf_experts(leaf)
        return ((self.k,) + ((e,) if e else ())
                + (leaf[1], n_sel, self.blocks[leaf[0]]))

    def _frozen(self, params, tokens):
        """The embedding and the frozen prefix, every segment in order, on
        rows of tokens: (x, each layer's sums)."""
        stacks = params["segments"]
        x = self._embed(params, jnp.asarray(tokens))
        sums = []
        for name, count in self.segs:
            for i in range(self.first if name == self.seg else count):
                y = self._layers[name](stacks[name], jnp.int32(i), x)
                x, s = y if self.aux else (y, None)
                sums.append(s)
        return x, sums

    def _aux(self, deltas, dense, train, sel, xs, frozen_sums, tokens):
        """(cotangent of the aux loss by each trainable layer's sums, scaled
        by the rows as the mean over rows takes it back; the aux loss)."""
        sums = None
        for x in xs:
            s = self._sums(deltas, dense, train, sel, x)
            sums = s if sums is None else _add(sums, s)
        value, grad = jax.value_and_grad(
            lambda t: self.ref.aux_loss(self.m, frozen_sums + t, tokens))(
            sums)
        return _scale(grad, float(len(xs))), float(value)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _scale(a, c):
    return jax.tree.map(lambda v: v * c, a)


@jax.jit
def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def first_grad_norms(opt, w0, w1, state1) -> dict:
    """Per leaf: the norm of the first gradient as the optimizer holds it
    after one step."""
    if opt["kind"] == "adamw":
        return {key_of(p): float(_norm(v)) / (1 - opt["beta1"])
                for p, v in state1["mu"].items()}
    return {key_of(p): float(_diff_norm(w0[p], w1[p])) / opt["learning_rate"]
            for p in w0}


def change_norms(w0, wn) -> dict:
    return {key_of(p): float(_diff_norm(w0[p], wn[p])) for p in w0}
