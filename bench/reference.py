"""Plain reference of the population replay the benchmark times.

It replays the first steps of a cell from the same weights, data, schedule
rows (``schedule.commuter_rows``) and batch draws as the program, written
from the method's description and importing nothing of the program:

ML Mule, mobile mode, per step ``t`` (paper Fig. 2b):
  deliver  = exchange & in a space & active
  accept   = deliver & (space has < warmup receipts | age <= threshold)
  space f  <- (1 - g) f + g mean(accepted models at f)    (if any accepted)
  ages of every delivering mule are pushed, in mule order, into their
  space's ring of ``history``; threshold <- (1 - alpha) threshold
  + alpha (median + beta MAD) of the ring
  mule m   <- (1 - g) m + g (its space's new model)       (if delivering)
  mule m   <- one SGD step of m on its batch              (if delivering)
  mule_ts  <- t where delivering;  t <- t + 1

gossip, on steps ``t % every == every - 1``: every active mule with a peer
(same area, within ``radius``, active, not itself) mixes
``m <- (1 - g) m + g mean(peers)`` and takes one SGD step.

The batch of step ``t`` is the benchmark's draw with
``split(fold_in(key, t))[0]``: the engine's documented key discipline.
Freshness runs on the host in NumPy; the models on the device, with every
contraction at ``precision`` and every array in ``dtype`` (float32 at
``highest`` for the reference; bfloat16 for its control); integer inputs,
such as token ids, stay as they are. The loss is the reference file's
``loss(logits, y)`` where it defines one, else ``xent``. Mules train in
blocks of ``block`` so that the reference fits beside nothing else.

A configuration with frozen shared weights (its reference file defines
``init_shared``) has them in ``context["shared"]``: every mule's SGD step
reads that one base, cast to ``dtype`` like the models, through
``forward(params, x, precision, shared)``, and differentiates with respect
to its own trainable leaves only.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from schedule import commuter_rows


def xent(logits, y):
    """Mean cross-entropy of logits [B, C] against one label per example
    [B]. Other shapes raise: a configuration whose model gives other logits
    defines ``loss(logits, y)`` in its reference file."""
    if logits.ndim != 2 or y.shape != logits.shape[:1]:
        raise ValueError(f"xent takes logits [B, C] and labels [B], not "
                         f"{logits.shape} and {y.shape}: the reference file "
                         f"gives the loss of other shapes as loss(logits, y)")
    z = logits - logits.max(-1, keepdims=True)
    logp = z - jnp.log(jnp.exp(z).sum(-1, keepdims=True))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def loss_of(ref) -> Callable:
    """The configuration's reference loss: its file's ``loss``, else
    ``xent``."""
    return getattr(ref, "loss", xent)


def _median(vals: np.ndarray) -> np.float32:
    """Median of a 1-D float32 array: the midpoint of the two middle
    values for an even count."""
    s = np.sort(vals)
    n = s.shape[0]
    return np.float32(0.5) * (s[(n - 1) // 2] + s[n // 2])


class Freshness:
    """The paper's freshness filter (Sec 3.1) on the host, float32."""

    def __init__(self, n_fixed: int, f: Dict[str, Any]):
        self.f = f
        self.ages = np.full((n_fixed, f["history"]), np.float32(1e30))
        self.count = np.zeros((n_fixed,), np.int32)
        self.threshold = np.full((n_fixed,), np.float32(f["init_threshold"]))

    def accept(self, fid, ages, deliver):
        f = np.maximum(fid, 0)
        warm = self.count[f] < self.f["warmup"]
        return deliver & (warm | (ages <= self.threshold[f]))

    def push(self, fid, ages, deliver):
        k = self.f["history"]
        for m in np.nonzero(deliver)[0]:
            f = fid[m]
            self.ages[f, self.count[f] % k] = ages[m]
            self.count[f] += 1
        keep = np.float32(1 - self.f["alpha"])
        alpha = np.float32(self.f["alpha"])
        beta = np.float32(self.f["beta"])
        for f in range(self.ages.shape[0]):
            valid = self.ages[f][self.ages[f] < np.float32(1e30)]
            if valid.size == 0:
                continue
            med = _median(valid)
            mad = _median(np.abs(valid - med))
            self.threshold[f] = (keep * self.threshold[f]
                                 + alpha * (med + beta * mad))

    def state(self):
        return {"ages": self.ages.copy(), "count": self.count.copy(),
                "threshold": self.threshold.copy()}


def freshness_over_calls(tr, draws, calls):
    """The spaces' receipt counts and age rings (ML Mule) after consecutive
    calls of the entry point of ``calls`` steps each: the schedule starts
    at step 0 in every call, the population's step counter runs on. Neither
    depends on the models: every delivering mule's age is pushed, accepted
    or not."""
    fresh = Freshness(tr["spaces"], tr["freshness"])
    mule_ts = np.zeros((tr["mules"],), np.float32)
    t = 0
    for n in calls:
        r = commuter_rows(draws, tr["mobility"], 0, n)
        for s in range(n):
            fid = r["fixed_id"][s]
            deliver = r["exchange"][s] & (fid >= 0) & r["active"][s]
            fresh.push(fid, np.float32(t) - mule_ts, deliver)
            mule_ts = np.where(deliver, np.float32(t), mule_ts)
            t += 1
    out = fresh.state()
    return {"count": out["count"], "ages": out["ages"]}


def _mix(a, b, g):
    """a <- (1 - g) a + g b, ``g`` per population member."""
    return jax.tree.map(
        lambda x, y: (1 - g.reshape((-1,) + (1,) * (x.ndim - 1))) * x
        + g.reshape((-1,) + (1,) * (x.ndim - 1)) * y, a, b)


class Population:
    """Replays a cell's first steps. ``train(p, x, y[, shared])`` may
    replace the SGD step of one mule (the fault readings plant theirs
    there); it is given the base where the configuration has one."""

    def __init__(self, cell, ref, context, key, draws, *, dtype=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST, block: int = 128,
                 train: Optional[Callable] = None):
        self.cell, self.tr, self.cfg = cell, cell.traffic, cell.config
        self.dtype, self.precision = dtype, precision
        self.draws, self.key = draws, key
        cast = lambda l: (l.astype(dtype) if jnp.issubdtype(l.dtype,
                                                              jnp.floating)
                          else l)
        self.ctx = {"x": cast(context["x"]), "y": context["y"],
                    "pools": context["pools"]}
        self.shared = (jax.tree.map(cast, context["shared"])
                       if "shared" in context else None)
        m = self.tr["mules"]
        self.block = min(block, m)
        if m % self.block:
            raise ValueError(f"{m} mules do not split into blocks of "
                             f"{self.block}")
        lr, batch = self.cfg["lr"], self.cfg["batch"]
        fwd, loss = ref.forward, loss_of(ref)

        def sgd(p, x, y, *shared):
            g = jax.grad(lambda q: loss(fwd(q, x, precision, *shared)
                                        .astype(jnp.float32), y))(p)
            return jax.tree.map(lambda a, b: a - jnp.asarray(lr, dtype) * b,
                                p, g)

        self.train = train or sgd
        self.batch = batch
        self._step = jax.jit(self._device_step)
        self._gossip = jax.jit(self._gossip_step)

    # -- pieces ------------------------------------------------------------

    def _batch_rows(self, t):
        from program import batch_index
        kb = jax.random.split(jax.random.fold_in(self.key, t))[0]
        return batch_index(kb, self.ctx["pools"], self.batch)

    def _train_all(self, models, idx, x, y, shared):
        """SGD step of every mule, ``block`` mules at a time; every mule
        reads the one ``shared`` base."""
        m, b = idx.shape[0], self.block
        blocks = jax.tree.map(lambda l: l.reshape((m // b, b) + l.shape[1:]),
                              models)
        train = (self.train if shared is None else
                 lambda p, xx, yy: self.train(p, xx, yy, shared))

        def one(args):
            p, i = args
            return jax.vmap(train)(p, x[i], y[i])

        out = jax.lax.map(one, (blocks, idx.reshape(m // b, b, -1)))
        return jax.tree.map(lambda l: l.reshape((m,) + l.shape[2:]), out)

    def _device_step(self, mule, fixed, fid, accept, deliver, idx, x, y,
                     shared):
        n_fixed = self.tr["spaces"]
        g = self.dtype(self.tr["gamma"])
        f = jnp.maximum(fid, 0)
        assign = (jnp.arange(n_fixed)[:, None] == f[None]) & accept[None]
        assign = assign.astype(jnp.float32)
        mass = assign.sum(1)
        norm = (assign / jnp.maximum(mass, 1e-12)[:, None]).astype(self.dtype)
        agg = jax.tree.map(lambda l: jnp.einsum(
            "fm,m...->f...", norm, l, precision=self.precision), mule)
        has = (mass > 0).astype(self.dtype)
        fixed = _mix(fixed, agg, g * has)
        d = deliver.astype(self.dtype)
        mule = _mix(mule, jax.tree.map(lambda l: l[f], fixed), g * d)
        mule = _mix(mule, self._train_all(mule, idx, x, y, shared), d)
        return mule, fixed

    def _gossip_step(self, mule, area, active, pos, idx, x, y, shared):
        meth = self.tr["method"]
        d2 = ((pos[:, None] - pos[None]) ** 2).sum(-1)
        enc = ((area[:, None] == area[None]) & (d2 <= meth["radius"] ** 2)
               & active[:, None] & active[None]
               & ~jnp.eye(area.shape[0], dtype=bool)).astype(jnp.float32)
        mass = enc.sum(1)
        norm = (enc / jnp.maximum(mass, 1e-12)[:, None]).astype(self.dtype)
        mixed = jax.tree.map(lambda l: jnp.einsum(
            "mn,n...->m...", norm, l, precision=self.precision), mule)
        met = (mass > 0).astype(self.dtype)
        mule = _mix(mule, mixed, self.dtype(meth["gamma"]) * met)
        return _mix(mule, self._train_all(mule, idx, x, y, shared), met)

    # -- replay ------------------------------------------------------------

    def run(self, mule, fixed, n_steps: int):
        """Steps ``0 .. n_steps`` from these weights. Returns the models and
        the host freshness state."""
        cast = lambda t: jax.tree.map(lambda l: jnp.asarray(l, self.dtype), t)
        mule, fixed = cast(mule), cast(fixed)
        tr = self.tr
        fresh = Freshness(tr["spaces"], tr["freshness"])
        mule_ts = np.zeros((tr["mules"],), np.float32)
        x, y = self.ctx["x"], self.ctx["y"]
        name = tr["method"]["name"]
        for t in range(n_steps):
            r = commuter_rows(self.draws, tr["mobility"], t, 1)
            fid, act = r["fixed_id"][0], r["active"][0]
            idx = self._batch_rows(t)
            if name == "mlmule":
                deliver = r["exchange"][0] & (fid >= 0) & act
                ages = np.float32(t) - mule_ts
                accept = fresh.accept(fid, ages, deliver)
                fresh.push(fid, ages, deliver)
                mule, fixed = self._step(mule, fixed, fid, accept, deliver,
                                         idx, x, y, self.shared)
                mule_ts = np.where(deliver, np.float32(t), mule_ts)
            elif name == "gossip":
                every = tr["method"]["peer_every"]
                if t % every == every - 1:
                    mule = self._gossip(mule, r["area"], act, r["pos"][0],
                                        idx, x, y, self.shared)
            else:
                raise ValueError(f"no reference for method {name!r}")
        return mule, fixed, fresh.state()
