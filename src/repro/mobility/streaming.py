"""Streaming colocation generators: the schedule never materializes [T, M].

The scan engine historically replayed a *precomputed* ``[T, M]`` colocation
schedule — at M=10^6 that tensor alone dwarfs the population state. The
generators here emit the schedule chunk by chunk *inside* the compiled
replay (``repro.scenarios.run_population_streamed``), so host and device
memory for the schedule is O(chunk * M) plus O(M * segments) of compact
per-mule parameters, never O(T * M).

The generator contract
----------------------
A chunk generator is an object with

- ``n_mules``/``n_steps``  — population size and nominal horizon;
- ``arrays()``             — a pytree of device arrays (the compact
  schedule / per-mule parameters). Passed to the compiled chunk program as
  *traced inputs*, so two generators with the same shapes share one
  executable; under ``shard_map`` each leaf shards per ``specs()``, so a
  shard's expansion touches only its own mule columns;
- ``specs(axis)``          — matching pytree of ``PartitionSpec`` for the
  distributed engine (mule-leading leaves shard, the rest replicate);
- ``static_token()``       — hashable tuple of everything *baked into the
  trace* (periods, cadences, flags). Joins the engine's jit-cache key
  together with the array signature — deliberately **excluding** the
  horizon ``n_steps``, so replays of different lengths reuse one compiled
  chunk program;
- ``generate_chunk(key, t0, chunk_len)`` — the hot path: pure ``jnp``
  math (traceable, no host NumPy), returning ``{"fixed_id": [c, n] int32,
  "exchange": [c, n] bool, "pos": [c, n, 2] f32, "area": [n] int32 (or
  [c, n] when the schedule's areas move — migratory traces),
  "active": [c, n] bool}`` for global steps ``t0 .. t0+chunk_len``.
  ``key`` is an optional override PRNG key; the builders below bake their
  seed at build time and ignore it, which is what makes a streamed replay
  and a materialized reference of the same generator bitwise-identical.
  ``expand(arrays, key, t0, chunk_len)`` is the same computation with the
  array pytree passed explicitly (what the engine traces).

Three families:

- :func:`dense_colocation` wraps a materialized ``[T, M]`` colocation dict
  as-is: ``expand`` slices it. It is how ``run_population``,
  ``run_population_distributed`` and ``run_sweep`` reach the engine's one
  compiled chunk replay, as a single chunk of length ``T``.
- :func:`compact_colocation` losslessly compacts ANY materialized
  colocation dict into per-mule run-length segments and expands them
  on-device — bitwise-equal to the host tensors by construction, chunk
  boundaries included. This is how every *registered* scenario streams.
  Exchange flags are re-derived closed-form from run starts and the dwell
  cadence whenever that reproduces the input exactly (it does for every
  trace/walk scenario — they are all dwell-cadence schedules), falling
  back to a verbatim RLE of the exchange columns otherwise.
- :func:`commuter_stream` is fully procedural: O(M) per-mule parameters
  drawn once with ``jax.random`` at build time, closed-form schedule per
  ``(t, mule)`` in the hot path — the generator the M=10^5..10^6 scale
  sweep (``benchmarks/engine_micro.run_scale_bench``) runs, since its
  memory is independent of T entirely.

``materialize_generator`` turns any generator back into the classic
numpy colocation dict — the O(T * M) parity reference, playing the role
``run_population_loop`` plays for the scan.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.mobility.trace import dwell_exchange_flags

# padding sentinel for RLE start times: larger than any reachable step but
# safely below int32 overflow when compared against t0 + chunk offsets
_PAD_T = np.iinfo(np.int32).max // 2


def _rle_columns(arr: np.ndarray, pad_val) -> Tuple[np.ndarray, np.ndarray]:
    """Per-column run-length encoding of a ``[T, M]`` array.

    Returns ``(starts [M, S] int32, values [M, S])`` where column ``m``'s
    runs are ``values[m, i]`` from step ``starts[m, i]`` (inclusive) to the
    next start; ``S`` is the max run count over columns and shorter columns
    pad with ``(_PAD_T, pad_val)`` entries that no in-range step selects.
    """
    t_len, m = arr.shape
    change = np.ones((t_len, m), bool)
    change[1:] = arr[1:] != arr[:-1]
    counts = change.sum(axis=0)
    s = int(counts.max()) if m else 1
    cols, rows = np.nonzero(change.T)          # sorted by column, then step
    slot = np.arange(len(rows)) - np.repeat(np.cumsum(counts) - counts,
                                            counts)
    starts = np.full((m, s), _PAD_T, np.int32)
    values = np.full((m, s), pad_val, arr.dtype)
    starts[cols, slot] = rows
    values[cols, slot] = arr[rows, cols]
    return starts, values


def _expand_rle(starts: jnp.ndarray, values: jnp.ndarray,
                ts: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Evaluate per-mule step functions at steps ``ts``.

    ``starts``/``values``: ``[n, S]``; ``ts``: ``[c]``. Returns
    ``(vals [c, n], run_start [c, n])`` — the run value at each step and
    the step its run began (feeds the closed-form dwell cadence).
    """
    idx = jax.vmap(
        lambda s: jnp.searchsorted(s, ts, side="right") - 1)(starts)  # [n, c]
    vals = jnp.take_along_axis(values, idx, axis=1)
    run_start = jnp.take_along_axis(starts, idx, axis=1)
    return vals.T, run_start.T


class CompactColocation:
    """Exact compact form of a materialized colocation dict.

    Per-mule RLE segments for ``fixed_id`` (and, when present, the churn
    mask), closed-form dwell-cadence exchange (or RLE fallback), zeros or
    dense pass-through for ``pos``. ``generate_chunk`` reproduces the
    source tensors bitwise at any chunk boundary: integer/boolean RLE
    expansion is exact, and the cadence formula is only used when build-time
    verification proved it reproduces the input exchange exactly.
    """

    def __init__(self, n_mules: int, n_steps: int, arrays: Dict[str, Any],
                 *, cadence_scalar: Optional[int], has_active: bool,
                 has_exchange_rle: bool, has_dense_pos: bool,
                 has_area_rle: bool = False, max_area: int = 0):
        self.n_mules = int(n_mules)
        self.n_steps = int(n_steps)
        self.max_area = int(max_area)
        self._arrays = arrays
        self._cadence_scalar = cadence_scalar
        self._has_active = has_active
        self._has_exchange_rle = has_exchange_rle
        self._has_dense_pos = has_dense_pos
        self._has_area_rle = has_area_rle

    def arrays(self) -> Dict[str, Any]:
        return self._arrays

    def specs(self, axis: str):
        """PartitionSpecs per array leaf: mule-leading leaves shard."""
        from jax.sharding import PartitionSpec as P
        per_leaf = {
            "fid_starts": P(axis, None), "fid_vals": P(axis, None),
            "act_starts": P(axis, None), "act_vals": P(axis, None),
            "exc_starts": P(axis, None), "exc_vals": P(axis, None),
            "area_starts": P(axis, None), "area_vals": P(axis, None),
            "area": P(axis), "cadence": P(),
            "pos": P(None, axis, None),
        }
        return {k: per_leaf[k] for k in self._arrays}

    def static_token(self) -> Tuple:
        return ("compact", self._cadence_scalar, self._has_active,
                self._has_exchange_rle, self._has_dense_pos,
                self._has_area_rle)

    def schedule_bytes(self) -> int:
        """Bytes of compact schedule resident on device (O(M * segments))."""
        return sum(int(np.asarray(l).nbytes)
                   for l in jax.tree.leaves(self._arrays))

    def expand(self, arrays: Dict[str, Any], key, t0,
               chunk_len: int) -> Dict[str, Any]:
        del key                                  # deterministic from build
        ts = jnp.asarray(t0, jnp.int32) + jnp.arange(chunk_len, dtype=jnp.int32)
        fid, run_start = _expand_rle(arrays["fid_starts"],
                                     arrays["fid_vals"], ts)
        present = fid >= 0
        if self._has_exchange_rle:
            exch, _ = _expand_rle(arrays["exc_starts"], arrays["exc_vals"],
                                  ts)
        else:
            dwell = ts[:, None] - run_start + 1
            if self._cadence_scalar is not None:
                steps = jnp.int32(self._cadence_scalar)
            else:
                steps = arrays["cadence"][jnp.maximum(fid, 0)]
            exch = present & (dwell % steps == 0)
        if self._has_active:
            act, _ = _expand_rle(arrays["act_starts"], arrays["act_vals"],
                                 ts)
        else:
            act = jnp.ones(fid.shape, bool)
        n = fid.shape[1]
        if self._has_dense_pos:
            pos = jax.lax.dynamic_slice(
                arrays["pos"], (ts[0], 0, 0),
                (chunk_len, n, 2))
        else:
            pos = jnp.zeros((chunk_len, n, 2), jnp.float32)
        if self._has_area_rle:
            area, _ = _expand_rle(arrays["area_starts"],
                                  arrays["area_vals"], ts)
        else:
            area = arrays["area"]
        return {"fixed_id": fid, "exchange": exch, "pos": pos,
                "area": area, "active": act}

    def generate_chunk(self, key, t0, chunk_len: int) -> Dict[str, Any]:
        return self.expand(self._arrays, key, t0, chunk_len)


def compact_colocation(colocation: Dict[str, Any],
                       cadence=3) -> CompactColocation:
    """Compact a materialized colocation dict into a streaming generator.

    ``cadence`` is the dwell exchange tempo the schedule was built with (a
    scalar or the per-place array of a ``SpaceSpec`` scenario). The
    closed-form cadence expansion is *verified* against the input exchange
    tensor here on the host; a schedule whose exchange flags are not
    dwell-cadence-shaped (or whose cadence was guessed wrong) falls back to
    an exact RLE of the exchange columns — less compact, never wrong.
    """
    fid = np.asarray(colocation["fixed_id"], np.int32)
    exch = np.asarray(colocation["exchange"], bool)
    n_steps, n_mules = fid.shape
    arrays: Dict[str, Any] = {}

    fs, fv = _rle_columns(fid, np.int32(-1))
    arrays["fid_starts"] = jnp.asarray(fs)
    arrays["fid_vals"] = jnp.asarray(fv)

    cadence_scalar: Optional[int] = None
    has_exchange_rle = not np.array_equal(
        dwell_exchange_flags(fid, cadence), exch)
    if has_exchange_rle:
        es, ev = _rle_columns(exch, False)
        arrays["exc_starts"] = jnp.asarray(es)
        arrays["exc_vals"] = jnp.asarray(ev)
    elif np.ndim(cadence) == 0:
        cadence_scalar = int(cadence)
    else:
        arrays["cadence"] = jnp.asarray(np.asarray(cadence), jnp.int32)

    active = colocation.get("active")
    has_active = active is not None
    if has_active:
        as_, av = _rle_columns(np.asarray(active, bool), False)
        arrays["act_starts"] = jnp.asarray(as_)
        arrays["act_vals"] = jnp.asarray(av)

    pos = colocation.get("pos")
    has_dense_pos = pos is not None and np.asarray(pos).any()
    if has_dense_pos:
        arrays["pos"] = jnp.asarray(np.asarray(pos), jnp.float32)

    area = colocation.get("area")
    area = (np.zeros((n_mules,), np.int32) if area is None
            else np.asarray(area, np.int32))
    has_area_rle = area.ndim == 2
    if has_area_rle:
        ars, arv = _rle_columns(area, np.int32(0))
        arrays["area_starts"] = jnp.asarray(ars)
        arrays["area_vals"] = jnp.asarray(arv)
    else:
        arrays["area"] = jnp.asarray(area)

    return CompactColocation(n_mules, n_steps, arrays,
                             cadence_scalar=cadence_scalar,
                             has_active=has_active,
                             has_exchange_rle=has_exchange_rle,
                             has_dense_pos=has_dense_pos,
                             has_area_rle=has_area_rle,
                             max_area=int(area.max(initial=0)))


def _dev(x, dtype) -> jnp.ndarray:
    """To-device cast that never host-round-trips an existing device array.

    ``jnp.asarray(np.asarray(x))`` copies device arrays back to the host
    and up again — double-buffering ``[T, M]`` schedules for nothing. A
    ``jax.Array`` of the right dtype passes through untouched; the wrong
    dtype casts on device; everything else (numpy, lists) uploads once.
    """
    dtype = np.dtype(dtype)
    if isinstance(x, jax.Array):
        return x if x.dtype == dtype else x.astype(dtype)
    return jnp.asarray(np.asarray(x), dtype)


def _colocation_tensors(colocation):
    """Normalize a colocation dict to (fid, exch, pos, area, act) arrays.

    ``act`` is the per-step activity (churn) mask ``[T, M]`` bool from the
    ``"active"`` key; absent, it defaults to all-ones — the dense
    population. Because the mask is data (same shape/dtype either way), a
    dense and a churned run of the same schedule shape share one compiled
    replay. Inputs already on device stay on device (no host copy). Leading
    axes before ``[T, M]`` (a sweep's seed stack) pass through.
    """
    fid = _dev(colocation["fixed_id"], jnp.int32)
    exch = _dev(colocation["exchange"], bool)
    m = fid.shape[-1]
    pos = colocation.get("pos")
    pos = (jnp.zeros(fid.shape + (2,), jnp.float32) if pos is None
           else _dev(pos, jnp.float32))
    area = colocation.get("area")
    area = (jnp.zeros(fid.shape[:-2] + (m,), jnp.int32) if area is None
            else _dev(area, jnp.int32))
    act = colocation.get("active")
    act = (jnp.ones(fid.shape, bool) if act is None
           else _dev(act, bool))
    return fid, exch, pos, area, act


_DENSE_KEYS = ("fixed_id", "exchange", "pos", "area", "active")


class DenseColocation:
    """A materialized ``[T, M]`` schedule as a chunk generator.

    ``arrays()`` are the schedule's own tensors, on device; ``expand``
    slices ``chunk_len`` steps at ``t0`` out of them. This is how the
    materialized entry points (``run_population``,
    ``run_population_distributed``, ``run_sweep``) reach the one compiled
    chunk replay. Leading axes before ``[T, M]`` (a sweep's seed stack) are
    vmapped away by the engine before ``expand`` sees the arrays.
    """

    def __init__(self, arrays: Dict[str, Any]):
        self._arrays = arrays
        fid = arrays["fixed_id"]
        self.n_steps, self.n_mules = (int(d) for d in fid.shape[-2:])
        self._area_dyn = arrays["area"].ndim == fid.ndim

    @functools.cached_property
    def max_area(self) -> int:
        area = self._arrays["area"]
        return int(jnp.max(area)) if area.size else 0

    def arrays(self) -> Dict[str, Any]:
        return self._arrays

    def specs(self, axis: str):
        """PartitionSpecs per array leaf: the mule columns shard."""
        from jax.sharding import PartitionSpec as P
        cols = P(None, axis)
        return {"fixed_id": cols, "exchange": cols,
                "pos": P(None, axis, None),
                "area": cols if self._area_dyn else P(axis),
                "active": cols}

    def static_token(self) -> Tuple:
        return ("dense", self._area_dyn)

    def expand(self, arrays: Dict[str, Any], key, t0,
               chunk_len: int) -> Dict[str, Any]:
        del key
        t0 = jnp.asarray(t0, jnp.int32)

        def window(leaf):
            return jax.lax.dynamic_slice_in_dim(leaf, t0, chunk_len, axis=0)

        return {k: (arrays[k] if k == "area" and not self._area_dyn
                    else window(arrays[k])) for k in _DENSE_KEYS}

    def generate_chunk(self, key, t0, chunk_len: int) -> Dict[str, Any]:
        return self.expand(self._arrays, key, t0, chunk_len)


def dense_colocation(colocation: Dict[str, Any]) -> DenseColocation:
    """Wrap a materialized colocation dict as a chunk generator (see
    :class:`DenseColocation`). Missing ``pos``/``area``/``active`` fill in
    as zeros, zeros and all-ones; extra keys are ignored."""
    return DenseColocation(dict(zip(_DENSE_KEYS,
                                    _colocation_tensors(colocation))))


class CommuterStream:
    """Procedural counter-keyed commuter schedule: O(M) memory, any T.

    Per-mule home/work places, jitter phase, and (odd) day stride are drawn
    once at build time with pure ``jax.random``; the hot path derives the
    step's place from ``(t, mule)`` with integer math only. Day ``d`` of
    mule ``m`` looks like::

        [home   j) [commute) [work  work_len) [commute) [home   period)

    with ``j = (phase + d * stride) % (jitter + 1)`` — a per-(mule, day)
    jitter that is layout-independent, so a shard expanding only its own
    columns produces exactly the single-host columns. Exchange flags are
    the standard dwell cadence; an evening-home run that touches midnight
    *continues* into the next morning (the run start reaches back across
    the day boundary), so the flags agree bitwise with
    ``dwell_exchange_flags`` over the materialized grid — compacting a
    materialization of this generator round-trips exactly.

    Optional duty-cycle churn (``duty_period > 0``): mule ``m`` is active
    while ``(t + aphase[m]) % duty_period < duty_on``, with mule
    ``t % n_mules`` forced on so no step goes fully dark.
    """

    def __init__(self, seed: int, n_mules: int, n_steps: int, *,
                 n_places: int = 8, period: int = 192,
                 work_frac: float = 0.45, commute: int = 6, jitter: int = 8,
                 exchange_steps: int = 3, duty_period: int = 0,
                 duty_on_frac: float = 0.6):
        work_len = max(int(work_frac * period), 1)
        if jitter + 2 * commute + work_len >= period:
            raise ValueError(
                f"period={period} too short for jitter={jitter} + "
                f"2*commute={2 * commute} + work_len={work_len}")
        self.n_mules = int(n_mules)
        self.n_steps = int(n_steps)
        self.n_places = int(n_places)
        self.period = int(period)
        self.work_len = work_len
        self.commute = int(commute)
        self.jitter = int(jitter)
        self.exchange_steps = int(exchange_steps)
        self.max_area = (int(n_places) - 1) // 4
        self.duty_period = int(duty_period)
        self.duty_on = max(int(duty_on_frac * duty_period), 1) \
            if duty_period else 0

        kh, kw, kp, ks, ka = jax.random.split(jax.random.PRNGKey(seed), 5)
        m = self.n_mules
        home = jax.random.randint(kh, (m,), 0, n_places, jnp.int32)
        work = (home + jax.random.randint(kw, (m,), 1, n_places,
                                          jnp.int32)) % n_places
        self._arrays = {
            "home": home,
            "work": work,
            "phase": jax.random.randint(kp, (m,), 0, self.jitter + 1,
                                        jnp.int32),
            "stride": 2 * jax.random.randint(ks, (m,), 0, 1 << 15,
                                             jnp.int32) + 1,
            "ids": jnp.arange(m, dtype=jnp.int32),
        }
        if duty_period:
            self._arrays["aphase"] = jax.random.randint(
                ka, (m,), 0, duty_period, jnp.int32)

    def arrays(self) -> Dict[str, Any]:
        return self._arrays

    def specs(self, axis: str):
        from jax.sharding import PartitionSpec as P
        return {k: P(axis) for k in self._arrays}

    def static_token(self) -> Tuple:
        return ("commuter_stream", self.n_mules, self.n_places, self.period,
                self.work_len, self.commute, self.jitter,
                self.exchange_steps, self.duty_period, self.duty_on)

    def schedule_bytes(self) -> int:
        return sum(int(np.asarray(l).nbytes)
                   for l in jax.tree.leaves(self._arrays))

    def _day_jitter(self, day: jnp.ndarray, phase: jnp.ndarray,
                    stride: jnp.ndarray) -> jnp.ndarray:
        return (phase[None, :] + day[:, None] * stride[None, :]) \
            % (self.jitter + 1)

    def expand(self, arrays: Dict[str, Any], key, t0,
               chunk_len: int) -> Dict[str, Any]:
        del key                                  # deterministic from build
        p = self.period
        ts = jnp.asarray(t0, jnp.int32) + jnp.arange(chunk_len, dtype=jnp.int32)
        day, w = ts // p, ts % p                            # [c]
        phase, stride = arrays["phase"], arrays["stride"]
        j = self._day_jitter(day, phase, stride)            # [c, n]
        w0 = j + self.commute                               # work start
        w1 = w0 + self.work_len
        we = w1 + self.commute                              # evening start
        wb = w[:, None]
        morning, at_work, evening = wb < j, (wb >= w0) & (wb < w1), wb >= we
        fid = jnp.where(morning | evening, arrays["home"][None, :],
                        jnp.where(at_work, arrays["work"][None, :], -1))

        # run starts (absolute steps). The morning-home run continues the
        # previous evening's run when that evening existed (we < period),
        # matching host dwell semantics over the materialized grid.
        j_prev = self._day_jitter(day - 1, phase, stride)
        we_prev = j_prev + 2 * self.commute + self.work_len
        day_base = (day * p)[:, None]
        morning_start = jnp.where(
            (day[:, None] > 0) & (we_prev < p),
            day_base - p + we_prev, day_base)
        run_start = jnp.where(morning, morning_start,
                              jnp.where(at_work, day_base + w0,
                                        day_base + we))
        dwell = ts[:, None] - run_start + 1
        exch = (fid >= 0) & (dwell % self.exchange_steps == 0)

        if self.duty_period:
            act = ((ts[:, None] + arrays["aphase"][None, :])
                   % self.duty_period) < self.duty_on
            act = act | (arrays["ids"][None, :] == ts[:, None] % self.n_mules)
        else:
            act = jnp.ones(fid.shape, bool)
        pos = jnp.zeros((chunk_len, fid.shape[1], 2), jnp.float32)
        return {"fixed_id": fid.astype(jnp.int32), "exchange": exch,
                "pos": pos, "area": arrays["home"] // 4, "active": act}

    def generate_chunk(self, key, t0, chunk_len: int) -> Dict[str, Any]:
        return self.expand(self._arrays, key, t0, chunk_len)

    def init_fields(self) -> Dict[str, np.ndarray]:
        """init_space/init_area for the data partitioners (home-derived)."""
        home = np.asarray(self._arrays["home"])
        return {"init_space": (home % 4).astype(np.int64),
                "init_area": (home // 4).astype(np.int64)}


def commuter_stream(seed: int, n_mules: int, n_steps: int,
                    **kw) -> CommuterStream:
    """Build the procedural commuter generator (see :class:`CommuterStream`)."""
    return CommuterStream(seed, n_mules, n_steps, **kw)


def reorder_generator_arrays(generator, arrays: Dict[str, Any],
                             order) -> Dict[str, Any]:
    """Permute a generator's in-flight mule columns into a new bucket order.

    Leaves whose ``specs()`` entry shards over the mule axis are gathered
    along that axis with ``order`` (entry ``p`` names the source column for
    the mule now in slot ``p``); replicated leaves pass through untouched.
    This is what the streamed engine's mid-run re-bucketing applies to
    ``generator.arrays()`` at a swap, so every later ``expand`` emits its
    columns in the post-swap layout. The gather runs jitted so arrays
    placed across a multi-process mesh reorder in place (an eager gather
    rejects them); single-process results are bitwise unchanged.
    """
    order = np.asarray(order)
    sentinel = "_mule_"
    specs = generator.specs(sentinel)

    def one(spec, leaf):
        axes = tuple(spec)
        if sentinel in axes:
            return _axis_gather(leaf, order, axes.index(sentinel))
        return leaf

    return {k: one(specs[k], v) for k, v in arrays.items()}


@functools.partial(jax.jit, static_argnums=2)
def _axis_gather(leaf, order, axis):
    return jnp.take(leaf, jnp.asarray(order), axis=axis)


def materialize_generator(gen, n_steps: Optional[int] = None,
                          chunk_len: int = 256) -> Dict[str, np.ndarray]:
    """Expand a chunk generator into the classic numpy colocation dict.

    The O(T * M) reference: a streamed replay must be bitwise-equal to
    ``run_population`` over this dict, i.e. to the same compiled chunk
    program over ``dense_colocation`` of it (the scale bench asserts it per
    M). Includes ``init_space``/``init_area`` when the generator provides
    them.
    """
    n_steps = int(gen.n_steps if n_steps is None else n_steps)
    chunks = []
    for t0 in range(0, n_steps, chunk_len):
        c = gen.generate_chunk(None, t0, min(chunk_len, n_steps - t0))
        chunks.append({k: np.asarray(v) for k, v in c.items()})
    co = {k: np.concatenate([c[k] for c in chunks], axis=0)
          for k in ("fixed_id", "exchange", "pos", "active")}
    if chunks and chunks[0]["area"].ndim == 2:
        co["area"] = np.concatenate([c["area"] for c in chunks], axis=0)
    else:
        co["area"] = chunks[0]["area"] if chunks else np.zeros(
            (gen.n_mules,), np.int32)
    if hasattr(gen, "init_fields"):
        co.update(gen.init_fields())
    return co
