"""The plain reference of a federated run, and its control and faults.

What a cell's timed path computes, written out once more in plain
``jax.numpy``: the paper's CNN (5x5 SAME convs, ReLU, VALID max-pool, FC,
head), its initialiser, the clients' local SGD under the algorithm's
loss (FedAvg; FedMMD's multi-kernel MMD against the frozen global
stream), the top-k uplink with error feedback against an identity
downlink mirror, the server's example-weighted aggregation and the eval
over the whole test set.  It imports nothing of the program and takes
nothing the program made: it draws its own weights from the seed and its
own clients and batches from the seed's sampling stream.

``Recipe`` says how it computes: the reference itself at the precision
the configuration states (float32 parameters; convolutions and dense
layers at the TPU's default precision, one bfloat16 pass with float32
sums; MMD's distances, which are no matrix products, at full float32);
the control is the same in bfloat16, the precision below; the faults are
the breaks a later change could bring in (half of each batch left out of
the loss, the error feedback's residual dropped or stored in another
client's row).  Blocks of ten
clients train one after another, so a cohort of any size fits one chip.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# clients per jitted block: the largest cohort that trains at once
CLIENT_BLOCK = 10
EVAL_BLOCK = 2000


@dataclasses.dataclass(frozen=True)
class Recipe:
    dtype: str = "float32"          # "bfloat16": the control
    matmul: str = "default"         # "highest": six bf16 passes
    half_batch: bool = False        # fault: the loss sees half of a batch
    ef_fault: str = ""              # fault: "dropped" keeps no residual,
                                    # "wrong_rows" stores it one row off

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def precision(self):
        """The convolutions' and dense layers' precision."""
        return HIGHEST if self.matmul == "highest" else None


def for_config(config):
    """The reference at the precision a configuration states: its
    parameter dtype, and its convolutions and dense layers at its matmul
    precision (``default``: one bfloat16 pass, float32 sums)."""
    return Recipe(dtype=config["param_dtype"],
                  matmul=config["matmul_precision"])


CONTROL = Recipe(dtype="bfloat16")


# ---------------------------------------------------------------------------
# the sampling stream: which clients and which rows each round trains on
# ---------------------------------------------------------------------------

def sampling_stream(seed, sizes, rounds, cohort, steps, batch):
    """Per round: the cohort's client ids and, per client and local step,
    the rows of its shard, drawn as the federated data set of the repo
    draws them from ``numpy.random.default_rng(seed)``: ``choice`` of
    distinct ids, then one ``choice`` of rows per client and step."""
    n = len(sizes)
    if n > 4096:
        raise ValueError("federations above 4096 clients draw their "
                         "cohorts by another stream, not written here")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rounds):
        cids = rng.choice(n, size=cohort, replace=False)
        rows = [[rng.choice(sizes[c], size=batch, replace=sizes[c] < batch)
                 for _ in range(steps)] for c in cids]
        out.append((cids, rows))
    return out


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def feature_hw(m):
    h, w, _ = m["input_shape"]
    for _ in m["conv_channels"]:
        h = (h - m["pool_size"]) // m["pool_stride"] + 1
        w = (w - m["pool_size"]) // m["pool_stride"] + 1
    return h, w


def _trunc(key, shape, scale):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * scale


def init_state(m, seed):
    """The global state at round 0: ``{"model": ...}``, drawn from the
    seed as the program's initialiser draws it (truncated normal in
    [-2, 2], scaled by 1/(k sqrt(c_in)) for a conv and by 1/sqrt(fan_in)
    for a dense layer, zero biases)."""
    k_model, _ = jax.random.split(jax.random.PRNGKey(seed))
    n_conv, n_fc = len(m["conv_channels"]), len(m["fc_units"])
    keys = jax.random.split(k_model, n_conv + n_fc + 1)
    k = m["conv_kernel"]
    cin = m["input_shape"][-1]
    convs = []
    for i, cout in enumerate(m["conv_channels"]):
        kw = jax.random.split(keys[i])[0]
        convs.append({"w": _trunc(kw, (k, k, cin, cout),
                                  1.0 / (k * (cin ** 0.5))),
                      "b": jnp.zeros((cout,), jnp.float32)})
        cin = cout
    h, w = feature_hw(m)
    d = h * w * cin
    fcs = []
    for j, units in enumerate(m["fc_units"]):
        fcs.append({"w": _trunc(keys[n_conv + j], (d, units),
                                1.0 / math.sqrt(d)),
                    "b": jnp.zeros((units,), jnp.float32)})
        d = units
    head = {"w": _trunc(keys[-1], (d, m["n_classes"]), 1.0 / math.sqrt(d)),
            "b": jnp.zeros((m["n_classes"],), jnp.float32)}
    return {"model": {"convs": convs, "fcs": fcs, "head": head}}


def extract(m, params, x, prec):
    h = x
    for conv in params["convs"]:
        h = jax.lax.conv_general_dilated(
            h, conv["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=prec) + conv["b"]
        h = jax.nn.relu(h)
        p, s = m["pool_size"], m["pool_stride"]
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, p, p, 1),
                                  (1, s, s, 1), "VALID")
    return h


def head(params, feats, prec):
    h = feats.reshape(feats.shape[0], -1)
    for fc in params["fcs"]:
        h = jax.nn.relu(jnp.matmul(h, fc["w"], precision=prec) + fc["b"])
    return jnp.matmul(h, params["head"]["w"], precision=prec) \
        + params["head"]["b"]


def cross_entropy_terms(logits, y):
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


def mk_mmd2(x, y, widths):
    """Biased multi-kernel squared MMD with the mean cross squared
    distance (held constant) as the kernels' scale."""
    def d2(a, b):
        return jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)

    sigma = jax.lax.stop_gradient(jnp.mean(d2(x, y))) + 1e-8

    def kmean(a, b):
        dd = d2(a, b)
        return sum(jnp.mean(jnp.exp(-dd / (2.0 * w * sigma)))
                   for w in widths) / len(widths)

    return kmean(x, x) + kmean(y, y) - 2.0 * kmean(x, y)


# ---------------------------------------------------------------------------
# one federated run
# ---------------------------------------------------------------------------

class Run:
    """``rounds`` rounds of the cell from the seed, as the reference (or
    its control or a fault) computes them."""

    def __init__(self, model_cfg, traffic, recipe: Recipe):
        self.m = model_cfg
        self.t = traffic
        self.fl = traffic["fl"]
        self.r = recipe
        self._block = jax.jit(self._client_block)
        self._eval = jax.jit(self._eval_block)

    # -- losses ----------------------------------------------------------
    def _loss(self, trainable, global_state, x, y):
        m, fl, prec = self.m, self.fl, self.r.precision
        if self.r.half_batch:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        algo = fl["algorithm"]
        local = trainable["model"]
        feats = extract(m, local, x, prec)
        loss = jnp.mean(cross_entropy_terms(head(local, feats, prec), y))
        if algo == "fedmmd":
            f_g = jax.lax.stop_gradient(
                extract(m, global_state["model"], x, prec))
            loss = loss + fl["mmd_lambda"] * mk_mmd2(
                jnp.mean(feats, axis=(1, 2)), jnp.mean(f_g, axis=(1, 2)),
                tuple(fl["mmd_widths"])).astype(jnp.float32)
        elif algo != "fedavg":
            raise ValueError(f"no reference for algorithm {algo!r}")
        return loss

    def _local_train(self, global_state, xs, ys, lr):
        trainable = {"model": global_state["model"]}

        def step(tr, xy):
            loss, g = jax.value_and_grad(self._loss)(tr, global_state, *xy)
            tr = jax.tree.map(lambda p, gg: (p - lr * gg).astype(p.dtype),
                              tr, g)
            return tr, loss

        tr, losses = jax.lax.scan(step, trainable, (xs, ys))
        return tr, jnp.mean(losses)

    # -- the uplink ------------------------------------------------------
    def _encode(self, flat, ef):
        """One client's leaf delta -> (decoded delta, new EF residual)."""
        codec = self.fl["uplink_codec"]
        if codec == "identity":
            return flat, ef
        if codec == "topk":
            g = flat + ef
            k = max(1, int(round(self.fl["topk_frac"] * g.shape[0])))
            _, idx = jax.lax.top_k(jnp.abs(g), k)
            dec = jnp.zeros_like(g).at[idx].set(g[idx])
            return dec, g.at[idx].set(0)
        raise ValueError(f"no reference for uplink codec {codec!r}")

    def _client_block(self, global_state, bcast, xs, ys, ef_rows, lr):
        """A block of clients: each trains from ``bcast`` and, under a
        codec, encodes its delta; returns what the server aggregates."""
        def one(x, y, ef):
            start = dict(global_state, model=bcast)
            tr, loss = self._local_train(start, x, y, lr)
            out = {"loss": loss}
            if self.fl["uplink_codec"] == "identity" \
                    and self.fl["downlink_codec"] == "identity":
                out["model"] = tr["model"]
                return out, ef
            leaves, tree = jax.tree.flatten(
                jax.tree.map(jnp.subtract, tr["model"], bcast))
            dec, new_ef = [], []
            for i, leaf in enumerate(leaves):
                d, e = self._encode(leaf.reshape(-1), ef[i])
                dec.append(d.reshape(leaf.shape))
                new_ef.append(e)
            out["delta"] = jax.tree.unflatten(tree, dec)
            return out, new_ef

        return jax.vmap(one)(xs, ys, ef_rows)

    # -- the eval --------------------------------------------------------
    def _eval_block(self, state, x, y):
        m, prec = self.m, self.r.precision
        feats = extract(m, state["model"], x, prec)
        logits = head(state["model"], feats, prec)
        ce = cross_entropy_terms(logits, y)
        hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        return jnp.sum(ce), jnp.sum(hit)

    def evaluate(self, state, test):
        n = len(test["y"])
        ce = hit = 0.0
        for a in range(0, n, EVAL_BLOCK):
            x = jnp.asarray(test["x"][a:a + EVAL_BLOCK], self.r.jdtype)
            s_ce, s_hit = self._eval(state, x,
                                     jnp.asarray(test["y"][a:a + EVAL_BLOCK]))
            ce += float(s_ce)
            hit += float(s_hit)
        return ce / n, hit / n

    # -- the rounds ------------------------------------------------------
    def run(self, parts, test, seed, rounds):
        """Returns the initial and final global states (host arrays by
        leaf path) and, per round, the mean local loss, eval loss and
        accuracy."""
        fl, dt = self.fl, self.r.jdtype
        cast = lambda t: jax.tree.map(lambda a: a.astype(dt), t)  # noqa: E731
        state0 = init_state(self.m, seed)
        state = cast(state0)
        sizes = np.array([len(p["y"]) for p in parts])
        compressed = not (fl["uplink_codec"] == "identity"
                          and fl["downlink_codec"] == "identity")
        if fl["downlink_codec"] != "identity":
            raise ValueError("the reference writes the identity downlink "
                             "only")
        n_leaves = len(jax.tree.leaves(state["model"]))
        ef = [jnp.zeros((len(parts), a.size), dt)
              for a in jax.tree.leaves(state["model"])] \
            if fl["uplink_codec"] == "topk" else None
        mirror = state["model"]
        lr_at = lambda r: np.float32(fl["lr"] * fl["lr_decay"] ** r)  # noqa
        stream = sampling_stream(seed, sizes, rounds,
                                 fl["clients_per_round"], fl["local_steps"],
                                 fl["local_batch"])
        history = []
        for r, (cids, rows) in enumerate(stream):
            weights = sizes[cids].astype(np.float32)
            weights = weights / np.float32(weights.sum())
            lr = jnp.float32(lr_at(r))
            if compressed:
                bcast = jax.tree.map(lambda w, mm: mm + (w - mm),
                                     state["model"], mirror)
            else:
                bcast = state["model"]
            acc = None
            losses = []
            for b0 in range(0, len(cids), CLIENT_BLOCK):
                blk = list(range(b0, min(b0 + CLIENT_BLOCK, len(cids))))
                xs = jnp.asarray(np.stack([
                    np.stack([parts[cids[i]]["x"][rw] for rw in rows[i]])
                    for i in blk]), dt)
                ys = jnp.asarray(np.stack([
                    np.stack([parts[cids[i]]["y"][rw] for rw in rows[i]])
                    for i in blk]))
                idx = jnp.asarray(cids[blk])
                ef_rows = ([t[idx] for t in ef] if ef is not None
                           else [jnp.zeros((len(blk), 1), dt)] * n_leaves)
                out, new_ef = self._block(state, bcast, xs, ys, ef_rows, lr)
                if ef is not None and self.r.ef_fault != "dropped":
                    rows_to = ((idx + 1) % len(parts)
                               if self.r.ef_fault == "wrong_rows" else idx)
                    ef = [t.at[rows_to].set(e) for t, e in zip(ef, new_ef)]
                losses.extend(np.asarray(out.pop("loss"), np.float64))
                for j, i in enumerate(blk):
                    w = jnp.asarray(weights[i], dt)
                    contrib = jax.tree.map(lambda a: w * a[j], out)
                    acc = contrib if acc is None else jax.tree.map(
                        jnp.add, acc, contrib)
            new_state = {}
            if compressed:
                new_state["model"] = jax.tree.map(
                    lambda g, d: g + d.astype(g.dtype), state["model"],
                    acc["delta"])
                mirror = bcast
            else:
                new_state["model"] = acc["model"]
            state = new_state
            loss, accuracy = self.evaluate(state, test)
            history.append({"local_loss": float(np.mean(losses)),
                            "loss": loss, "acc": accuracy})
        return leaf_dict(state0), leaf_dict(state), history


def leaf_dict(state) -> Dict[str, np.ndarray]:
    """Leaves of a global state by path, e.g. ``model.convs.0.w``, as
    float64 host arrays."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        name = ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[name] = np.asarray(leaf, np.float64)
    return out


def run_reference(model_cfg, traffic, parts, test, seed, rounds, recipe):
    return Run(model_cfg, traffic, recipe).run(parts, test, seed, rounds)
