"""The paper's model-freshness filter (Sec 3.1).

Each fixed device f keeps a history L of the *ages* of models it has
received (age = now - model's last update time) and a dynamic threshold

    T_{t+1} = (1 - alpha) T_t + alpha * ( median(L) + beta * MAD(L) )

where MAD is the median absolute deviation. An incoming model is accepted
iff its age <= T (devices in warmup accept everything).

The paper does not give alpha/beta values; defaults alpha=0.1, beta=1.0 are
our documented assumption. History is a fixed ring buffer per device.

Two statistics back the threshold:

- the exact ring buffer (``init_freshness`` / ``push_and_update``) — the
  single-host engine's path. The push keeps the serial ring's slot order
  (mule order within a step) through each receipt's rank among the same
  step's receipts to its device, computed for all mules at once. A push
  split across population shards would still need that rank as a prefix
  count across shards, so the ring is not merged with a ``psum``.
- an associative histogram sketch (``init_freshness_sketch`` /
  ``sketch_push_and_update``) — ages are binned into a fixed per-device
  histogram; per-step shard contributions are plain sums, so the
  distributed engine merges them with one ``psum`` and recovers
  median/MAD from the merged histogram (``sketch_median_mad``) to
  interpolated-bin accuracy. ``FreshnessConfig.stat`` selects between the
  sketch (``"median"``, paper semantics) and the legacy ``"meanstd"``
  mean/std deviation the distributed engine used before.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# np, not jnp: a module-level jnp scalar would initialize the jax backend
# at import time, which breaks jax.distributed bring-up (initialize()
# must run before the first computation); as a traced constant the two
# are bitwise identical
INF = np.float32(1e30)


@dataclasses.dataclass(frozen=True)
class FreshnessConfig:
    alpha: float = 0.1
    beta: float = 1.0
    history: int = 16         # ring buffer length K
    warmup: int = 4           # accept-all until this many receipts
    init_threshold: float = 1e6
    # distributed-engine statistic: "median" (associative histogram sketch,
    # matches the paper's Sec 3.1 median/MAD) or "meanstd" (per-step
    # mean/std EMA — the engine's former documented deviation; carries no
    # receipt counts, so ``warmup`` is ignored there). The single-host
    # engine always uses the exact ring buffer above.
    stat: str = "median"
    sketch_bins: int = 64     # histogram resolution B (error ~ max_age/B)
    sketch_max_age: float = 512.0  # ages above clamp into the last bin


def init_freshness(n_fixed: int, cfg: FreshnessConfig):
    return {
        "ages": jnp.full((n_fixed, cfg.history), INF),     # ring buffer of ages
        "count": jnp.zeros((n_fixed,), jnp.int32),
        "threshold": jnp.full((n_fixed,), cfg.init_threshold, jnp.float32),
    }


def accept_mask(state, fixed_ids: jnp.ndarray, ages: jnp.ndarray,
                cfg: FreshnessConfig) -> jnp.ndarray:
    """fixed_ids: [M] target device per mule (-1 = none); ages: [M]."""
    fid = jnp.maximum(fixed_ids, 0)
    thr = state["threshold"][fid]
    warm = state["count"][fid] < cfg.warmup
    return (fixed_ids >= 0) & (warm | (ages <= thr))


def _masked_median(vals: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Median over valid entries of each row (midpoint for even counts)."""
    filled = jnp.where(valid, vals, INF)
    srt = jnp.sort(filled, axis=-1)
    n = jnp.sum(valid, axis=-1)                           # [F]
    lo = jnp.maximum(n - 1, 0) // 2
    hi = jnp.maximum(n, 1) // 2
    vlo = jnp.take_along_axis(srt, lo[:, None], axis=-1)[:, 0]
    vhi = jnp.take_along_axis(srt, hi[:, None], axis=-1)[:, 0]
    return 0.5 * (vlo + vhi)


def push_and_update(state, fixed_ids: jnp.ndarray, ages: jnp.ndarray,
                    deliver: jnp.ndarray, cfg: FreshnessConfig):
    """Push delivered ages into per-device rings, then update thresholds.

    fixed_ids/ages/deliver: [M] per-mule target, age, delivering-this-step.
    The rings come out as if the mules pushed one at a time in mule order:
    a receipt's rank ``r`` among this step's receipts to its device ``f``
    puts it at slot ``(count[f] + r) % K``, and of ``n`` receipts only the
    last ``K`` ranks survive (each earlier one is overwritten by the
    receipt ``K`` ranks after it). The survivors of one device hold
    distinct slots, so the write is a select over the [M, F, K] slot
    one-hot with no order among writes. Ages move as their int32 bits and
    ranks are integer prefix counts: nothing in the push rounds.
    """
    k = cfg.history
    n_fixed = state["count"].shape[0]
    d = deliver & (fixed_ids >= 0)
    hit = d[:, None] & (jnp.maximum(fixed_ids, 0)[:, None]
                        == jnp.arange(n_fixed)[None, :])          # [M, F]
    hit_i = hit.astype(jnp.int32)
    rank = jnp.cumsum(hit_i, axis=0) - hit_i      # same-device receipts before
    n = jnp.sum(hit_i, axis=0)                                    # [F]
    keep = hit & (rank >= n - k)
    slot = (state["count"] + rank) % k
    sel = keep[:, :, None] & (slot[:, :, None] == jnp.arange(k))  # [M, F, K]
    bits = jax.lax.bitcast_convert_type(ages.astype(jnp.float32), jnp.int32)
    new_bits = jnp.sum(jnp.where(sel, bits[:, None, None], 0), axis=0)
    ages_buf = jnp.where(
        jnp.any(sel, axis=0),
        jax.lax.bitcast_convert_type(new_bits, jnp.float32), state["ages"])
    return {"ages": ages_buf, "count": state["count"] + n,
            "threshold": update_threshold(state["threshold"], ages_buf, cfg)}


def update_threshold(threshold: jnp.ndarray, ages_buf: jnp.ndarray,
                     cfg: FreshnessConfig) -> jnp.ndarray:
    """One EMA step of each device's threshold toward median + beta * MAD
    of its ring ``ages_buf`` [F, K]; a device with an empty ring keeps its
    threshold."""
    valid = ages_buf < INF
    med = _masked_median(ages_buf, valid)
    mad = _masked_median(jnp.abs(ages_buf - med[:, None]), valid)
    target = med + cfg.beta * mad
    return jnp.where(jnp.any(valid, axis=-1),
                     (1 - cfg.alpha) * threshold + cfg.alpha * target,
                     threshold)


# ---------------------------------------------------------------------------
# associative median/MAD sketch (distributed engine)
# ---------------------------------------------------------------------------
#
# A per-device age histogram over B fixed bins. Binning is a sum, so shard
# contributions merge under ``psum``; median and MAD are then weighted
# quantiles of the merged histogram, exact to within one bin width. The ring
# buffer's last-K window is emulated by capping the resident histogram mass
# at K after each push (old receipts decay geometrically instead of being
# evicted slot-by-slot — the one semantic difference from the exact ring).


def sketch_edges(cfg: FreshnessConfig) -> jnp.ndarray:
    """Bin edges [B+1]: uniform over [0, sketch_max_age]."""
    return jnp.linspace(0.0, cfg.sketch_max_age, cfg.sketch_bins + 1)


def sketch_centers(cfg: FreshnessConfig) -> jnp.ndarray:
    e = sketch_edges(cfg)
    return 0.5 * (e[:-1] + e[1:])


def age_bin_onehot(ages: jnp.ndarray, cfg: FreshnessConfig) -> jnp.ndarray:
    """One-hot bin membership per age: [...] -> [..., B].

    Ages below 0 / above ``sketch_max_age`` clamp into the edge bins, so no
    mass is lost (the threshold comparison saturates the same way).
    """
    b = cfg.sketch_bins
    width = cfg.sketch_max_age / b
    idx = jnp.clip(jnp.floor(ages / width).astype(jnp.int32), 0, b - 1)
    return jax.nn.one_hot(idx, b, dtype=jnp.float32)


def age_histogram(ages: jnp.ndarray, weights: jnp.ndarray,
                  cfg: FreshnessConfig) -> jnp.ndarray:
    """Weighted histogram over the trailing axis: [..., N] -> [..., B]."""
    onehot = age_bin_onehot(ages, cfg)                          # [..., N, B]
    return jnp.sum(onehot * weights[..., None].astype(jnp.float32), axis=-2)


def hist_quantile(hist: jnp.ndarray, edges: jnp.ndarray,
                  q: float) -> jnp.ndarray:
    """Interpolated weighted quantile per row: hist [..., B] -> [...]."""
    c = jnp.cumsum(hist, axis=-1)
    total = c[..., -1:]
    t = q * total
    idx = jnp.argmax(c >= t, axis=-1)                           # first cross
    cprev = jnp.where(
        idx > 0,
        jnp.take_along_axis(c, jnp.maximum(idx - 1, 0)[..., None],
                            axis=-1)[..., 0], 0.0)
    mass = jnp.take_along_axis(hist, idx[..., None], axis=-1)[..., 0]
    frac = jnp.clip((t[..., 0] - cprev) / jnp.maximum(mass, 1e-12), 0.0, 1.0)
    width = edges[1] - edges[0]
    return edges[idx] + frac * width


def sketch_median_mad(hist: jnp.ndarray, cfg: FreshnessConfig):
    """(median, MAD) of the binned ages: hist [..., B] -> ([...], [...]).

    MAD is the weighted median of |bin center - median| — bins are sorted
    by distance from the median and the 0.5-mass crossing is taken.

    Accuracy: each estimate lands within one bin width of the sample order
    statistics bracketing the 0.5 quantile (``numpy``'s midpoint convention
    can sit anywhere inside that bracket, so on sparse histories the gap to
    ``jnp.median`` is bounded by the middle-sample spacing, and on dense
    histories both converge to bin resolution — tests pin both regimes).
    """
    edges = sketch_edges(cfg)
    med = hist_quantile(hist, edges, 0.5)
    d = jnp.abs(sketch_centers(cfg) - med[..., None])           # [..., B]
    order = jnp.argsort(d, axis=-1)
    ds = jnp.take_along_axis(d, order, axis=-1)
    ws = jnp.take_along_axis(hist, order, axis=-1)
    cw = jnp.cumsum(ws, axis=-1)
    total = cw[..., -1:]
    idx = jnp.argmax(cw >= 0.5 * total, axis=-1)
    mad = jnp.take_along_axis(ds, idx[..., None], axis=-1)[..., 0]
    return med, mad


def init_freshness_sketch(n_fixed: int, cfg: FreshnessConfig):
    return {
        "hist": jnp.zeros((n_fixed, cfg.sketch_bins), jnp.float32),
        "count": jnp.zeros((n_fixed,), jnp.int32),
        "threshold": jnp.full((n_fixed,), cfg.init_threshold, jnp.float32),
    }


def sketch_push_and_update(state, step_hist: jnp.ndarray,
                           step_counts: jnp.ndarray, cfg: FreshnessConfig):
    """Fold one step's (already psum-merged) histogram into the sketch.

    step_hist [F, B] / step_counts [F]: this step's delivered-age histogram
    and receipt counts, summed across population shards by the caller. The
    update itself runs on replicated state, so every shard computes the
    identical new sketch.
    """
    hist = state["hist"] + step_hist
    count = state["count"] + step_counts.astype(jnp.int32)
    total = jnp.sum(hist, axis=-1)
    # cap resident mass at the ring depth K: the sketch's last-K window
    scale = jnp.where(total > cfg.history,
                      cfg.history / jnp.maximum(total, 1e-12), 1.0)
    hist = hist * scale[:, None]
    med, mad = sketch_median_mad(hist, cfg)
    target = med + cfg.beta * mad
    new_thr = jnp.where(
        jnp.sum(hist, axis=-1) > 0,
        (1 - cfg.alpha) * state["threshold"] + cfg.alpha * target,
        state["threshold"])
    return {"hist": hist, "count": count, "threshold": new_thr}
