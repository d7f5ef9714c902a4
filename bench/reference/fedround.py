"""The first rounds of a FedOptima run, computed plainly.

One round (Alg. 1 and 4 of the paper, as the pod maps them): for each of
H micro-iterations, the server trains one SGD step on the activation
batch in the omega = 1 ring (written by the previous micro-iteration;
rows never written are left out of its loss), and every group trains its
device layers and aux head one SGD step on its own rows, whose device
activations (from the weights before the step) then fill the ring.  At
the end of the round the groups' device layers and aux heads are
averaged with equal weights (every group took part, none is stale) and
the average goes back to every group.

Gradients are taken layer by layer (forward keeping each layer's input,
then one ``jax.vjp`` per layer in reverse) and row by row, so that
nothing larger than one layer of one sequence is live at once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import data, model


def path_of(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def named_leaves(tree, prefix: str = "") -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {prefix + path_of(kp): x for kp, x in flat}


class Reference:
    """Runs ``rounds`` rounds of one cell's traffic from the seed.

    ``dtype`` is the precision every weight is held in and updated;
    ``compute_dtype`` (default: ``dtype``) the one that weights and
    activations are cast to for the forward and backward passes;
    ``half_batch`` takes every loss over the first half of each row's
    tokens only, and ``aggregate=False`` leaves out the end-of-round
    average (two faults a program could have, planted here to read what
    they do to the check).
    """

    def __init__(self, config: dict, traffic: dict, *, dtype=jnp.float32,
                 compute_dtype=None, half_batch: bool = False,
                 aggregate: bool = True):
        self.a = model.Arch.from_config(config)
        t = traffic
        self.G = t["mesh"][0] * t["groups_per_shard"]
        self.H = t["H"]
        self.micro = t["per_group_batch"] // t["H"]
        self.seq = t["seq_len"]
        self.p_drop = t["p_drop"]
        self.lr_d, self.lr_s = t["lr_d"], t["lr_s"]
        if t["omega"] != 1 or t["server_opt"] != "sgd":
            raise ValueError("the reference covers an omega = 1 ring and "
                             "plain SGD")
        self.dtype = dtype
        self.cdt = dtype if compute_dtype is None else compute_dtype
        self.keep = self.seq // 2 if half_batch else self.seq
        self.aggregate = aggregate
        a = self.a
        blk = lambda p, h: model.block(self._c(p), h, a)
        self._fwd = jax.jit(blk)
        self._bwd = jax.jit(lambda p, h, g: jax.vjp(blk, p, h)[1](g))
        self._aux_vg = jax.jit(jax.value_and_grad(self._aux_loss, (0, 1)))
        self._srv_vg = jax.jit(jax.value_and_grad(self._srv_loss, (0, 1)))
        self._embed_grad = jax.jit(
            lambda e, tok, g: jnp.zeros_like(e).at[tok].add(g.astype(e.dtype)))
        self._sgd = jax.jit(lambda p, g, lr: jax.tree.map(
            lambda x, y: (x - lr * y).astype(x.dtype), p, g))
        self._mean = jax.jit(lambda *xs: (sum(x.astype(jnp.float32)
                                              for x in xs) / len(xs)))

    def _c(self, tree):
        """``tree`` in the compute precision (the cast's transpose brings
        its gradient back to the weights' precision)."""
        return jax.tree.map(lambda x: x.astype(self.cdt), tree)

    # -- losses (summed over tokens; divided by the count outside) -------
    def _aux_loss(self, aux, acts, labels):
        aux = self._c(aux)
        h = model.block(aux["block"], acts, self.a)
        h = model.rmsnorm(aux["norm"], h, self.a.eps)
        logits = (h @ aux["head_in"]) @ aux["head_out"]
        return model.ce_sum(logits[:, :self.keep], labels[:, :self.keep])

    def _srv_loss(self, head, h, labels):
        head = self._c(head)
        h = model.rmsnorm(head["final_norm"], h, self.a.eps)
        logits = h @ head["embed_out"].T
        return model.ce_sum(logits[:, :self.keep], labels[:, :self.keep])

    # -- state -----------------------------------------------------------
    def init(self, seed: int):
        """Per-group lists of per-layer weights, from the program's recipe."""
        a, G = self.a, self.G
        key = jax.random.PRNGKey(seed)
        dev, aux, srv = jax.jit(model.init_state,
                                static_argnums=(1, 2))(key, a, 1)
        cast = lambda t: jax.tree.map(lambda x: x.astype(self.dtype), t)
        layer = lambda stack, i: cast(jax.tree.map(lambda x: x[i], stack))
        one = lambda t: jax.tree.map(lambda x: x[0], t)
        dev, aux = one(dev), one(aux)
        groups = [{"layers": [layer(dev["blocks"][0], i)
                              for i in range(a.dev_layers)],
                   "embed": cast(dev["embed"]), "aux": cast(aux)}
                  for _ in range(G)]
        server = {"layers": [layer(srv["blocks"][0], i)
                             for i in range(a.n_layers - a.dev_layers)],
                  "head": cast({"final_norm": srv["final_norm"],
                                "embed_out": srv["embed_out"]})}
        return groups, server

    def leaves(self, groups, server) -> dict:
        """Named leaves in the program's layout: each program leaf maps to
        the list of reference arrays it stacks (over groups and layers)."""
        out: dict = {}

        def add(name, x):
            out.setdefault(name, []).append(x)

        for g in groups:
            for lay in g["layers"]:
                for k, x in named_leaves(lay, "dev/blocks/0/").items():
                    add(k, x)
            add("dev/embed", g["embed"])
            for k, x in named_leaves(g["aux"], "aux/").items():
                add(k, x)
        for lay in server["layers"]:
            for k, x in named_leaves(lay, "srv/blocks/0/").items():
                add(k, x)
        add("srv/final_norm/scale", server["head"]["final_norm"]["scale"])
        add("srv/embed_out", server["head"]["embed_out"])
        return out

    # -- one SGD step of a stack of layers plus a head, row by row -------
    def _stack_step(self, layers, head, vg, inputs, labels, count, lr):
        """One SGD step on the mean loss over ``inputs`` rows of (layers,
        head).  Returns the mean loss, the new layers and head, the loss
        gradient at each row's input and the stack's output rows.  Each
        layer is stepped as soon as its gradient (summed over the rows)
        is whole: the backward of the layers below reads only their own
        weights, so this is the plain step."""
        rows = range(inputs.shape[0])
        hs = []
        for i in rows:
            h = [inputs[i:i + 1]]
            for p in layers:
                h.append(self._fwd(p, h[-1]))
            hs.append(h)
        total, g_head, gx = 0.0, None, []
        for i in rows:
            loss, (gh, g) = vg(head, hs[i][-1], labels[i:i + 1])
            total = total + loss
            g_head = gh if g_head is None else jax.tree.map(jnp.add, g_head, gh)
            gx.append(g)
        new_head = self._sgd(head, jax.tree.map(lambda x: x / count, g_head),
                             lr)
        new_layers = list(layers)
        for j in range(len(layers) - 1, -1, -1):
            g_sum = None
            for i in rows:
                gp, gx[i] = self._bwd(layers[j], hs[i][j], gx[i])
                g_sum = gp if g_sum is None else jax.tree.map(jnp.add, g_sum, gp)
            new_layers[j] = self._sgd(
                layers[j], jax.tree.map(lambda x: x / count, g_sum), lr)
        outs = jnp.concatenate([h[-1] for h in hs])
        return (float(total) / count, new_layers, new_head,
                [g / count for g in gx], outs)

    def device_step(self, grp, tokens, labels):
        x = grp["embed"][jnp.asarray(tokens)].astype(self.cdt)
        count = float(tokens.shape[0] * self.keep)
        loss, layers, aux, gx, acts = self._stack_step(
            grp["layers"], grp["aux"], self._aux_vg, x, jnp.asarray(labels),
            count, self.lr_d)
        ge = self._embed_grad(grp["embed"], jnp.asarray(tokens),
                              jnp.concatenate(gx))
        new = {"layers": layers, "embed": self._sgd(grp["embed"], ge, self.lr_d),
               "aux": aux}
        return loss, new, acts

    def server_step(self, server, ring):
        if ring is None:
            return 0.0, 0.0, server
        acts, labels = ring
        count = float(acts.shape[0] * self.keep)
        loss, layers, head, _, _ = self._stack_step(
            server["layers"], server["head"], self._srv_vg, acts,
            jnp.asarray(labels), count, self.lr_s)
        return loss, 1.0, {"layers": layers, "head": head}

    def run_round(self, groups, server, ring, tokens, labels):
        d_losses, s_losses, live = [], [], []
        for h in range(self.H):
            s_loss, s_live, server = self.server_step(server, ring)
            s_losses.append(s_loss)
            live.append(s_live)
            losses, acts = [], []
            for g in range(self.G):
                loss, groups[g], act = self.device_step(
                    groups[g], tokens[g, h], labels[g, h])
                losses.append(loss)
                acts.append(act)
            d_losses.append(float(np.mean(losses)))
            ring = (jnp.concatenate(acts),
                    np.concatenate([labels[g, h] for g in range(self.G)]))
        if self.aggregate:
            avg = jax.tree.map(lambda *xs: self._mean(*xs).astype(xs[0].dtype),
                               *groups)
            groups = [avg for _ in range(self.G)]
        d = float(np.mean(d_losses))
        s = float(np.dot(s_losses, live) / max(sum(live), 1.0))
        return {"d_loss": d, "s_loss": s}, groups, server, ring

    def run(self, seed: int, rounds: int, on_round=None):
        """Losses of rounds 0..rounds-1; ``on_round(r, groups, server)``
        sees the state after each (r counts rounds done)."""
        streams = data.group_streams(self.G, self.a.vocab, seed)
        feed = data.rounds(streams, seed=seed, n_rounds=rounds, H=self.H,
                           micro=self.micro, seq=self.seq, p_drop=self.p_drop)
        prec = "highest" if self.cdt == jnp.float32 else "default"
        with jax.default_matmul_precision(prec):
            groups, server = self.init(seed)
            if on_round is not None:
                on_round(0, groups, server)
            ring, history = None, []
            for r, (tokens, labels) in enumerate(feed, start=1):
                m, groups, server, ring = self.run_round(groups, server, ring,
                                                         tokens, labels)
                history.append(m)
                if on_round is not None:
                    on_round(r, groups, server)
        return history
