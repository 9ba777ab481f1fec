"""Compiled scenario engine: method-dispatched scans with a jit cache.

The harness used to drive the simulation with a per-step Python loop — one
jitted dispatch per time step, thousands of dispatches per experiment. Here
the whole run is one (optionally chunked) ``lax.scan`` over precomputed
``[T, M]`` co-location tensors, with periodic evaluation *inside* the scan,
so a full scenario replay is a single XLA program. Every mobile-protocol
method (``repro.core.population.METHODS_MOBILE``) rides the same engine:
``method=`` selects the per-step update built by ``make_method_step`` (the
baselines' 3-step exchange cadence is a ``lax.cond`` on the step index).

Distributed replay
------------------
``run_population_distributed`` lifts the same scan — ``psum`` collective
schedule included — under ``shard_map`` over the mesh mule (``data``) axis:
mule state and colocation columns shard, fixed-device state replicates, and
``repro.core.distributed.make_distributed_method_step`` supplies the
step, so a mule-sharded experiment is ONE program instead of one
``shard_map`` dispatch per step (``run_population_distributed_loop``
preserves the per-step dispatch pattern as the parity/bench reference).
Every ``METHODS_MOBILE`` method lowers to the distributed
step through the one ``repro.core.method_program`` table — the
peer-encounter baselines cross shards via its ring ``ppermute``
exchange. Multi-seed sweeps compose: ``run_sweep_distributed`` stacks the
seed ``vmap`` axis *inside* the shard_map block (i.e. outside the mule
axis, unsharded), one program per method, bitwise-equal per lane to
sequential distributed runs.

Streaming replay
----------------
``run_population`` scans a *materialized* ``[T, M]`` schedule — at
M=10^5-10^6 the schedule dwarfs the population state.
``run_population_streamed`` replaces the precomputed xs with a chunk
generator (``repro.mobility.streaming``): each compiled dispatch expands
``chunk_len`` steps of colocation *inside the trace* from O(M)-ish compact
arrays and scans them, so schedule memory is O(chunk · M) regardless of
horizon. Its jit cache key hashes the generator signature + chunk shape,
never ``T`` — one compiled program serves any horizon — and state/last
buffers are donated per chunk (``donate_argnums=(0, 1)``). Under a mesh
the generator's arrays shard over the mule axis and each shard expands
only its own columns: the distributed engine never gathers a global
schedule. Parity: a streamed replay is bitwise-equal to ``run_population``
over ``materialize_generator(generator)``, chunk boundaries included,
because ``_scan_core`` is shared and every step keys off its *global*
index.

Jit cache
---------
``run_population`` used to retrace on every call — fine for one replay per
experiment, wasteful for sweeps. Compiled replays are now memoized in a
module-level cache keyed on everything that determines the traced program:

  (kind, method, cfg, eval_every, n_steps,
   train_fn, eval_fn, batch-callable identity,
   shape/dtype signatures of state, colocation tensors, stacked batches,
   context, and the PRNG key;
   plus donation, and — for the distributed kinds — mesh and the
   DistributedConfig)

``cfg`` hashes by value (frozen dataclass); functions hash by identity, so
reuse the *same* ``train_fn``/``batches``/``eval_fn`` objects across calls
to hit the cache (a fresh lambda per call means a fresh trace). The cache
holds strong references but is LRU-bounded (oldest entries evicted past
``_JIT_CACHE_MAX``), so loops that can never hit — e.g. a fresh closure
per experiment — don't accumulate executables and closure-captured data
for process lifetime; ``jit_cache_clear()`` resets it and
``jit_cache_stats()`` reports ``{"traces", "hits", "misses"}`` — the
traces counter increments only when XLA actually retraces, which is what
``benchmarks/engine_micro.py`` asserts goes to zero on repeat calls.

Key discipline (the parity tests rely on reproducing it exactly):

- step ``t`` uses ``k_t = jax.random.fold_in(key, t)``;
- if ``batches`` is a callable ``(key, t) -> batches-dict`` (or
  ``(key, t, context) -> batches-dict`` when a ``context`` pytree is
  passed), the step splits ``kb, ks = jax.random.split(k_t)`` and calls
  ``batches(kb, t[, context])``; the training key is ``ks``;
- if ``batches`` is a pytree of stacked ``[T, ...]`` leaves, step ``t``
  consumes slice ``t`` and trains with ``k_t`` directly.

``run_population_loop`` preserves the retired per-step driver verbatim as
the parity reference (the same role ``trace_to_colocation_loop`` plays for
the vectorized trace expansion): Python-level method dispatch, one jitted
call per step. Tests pin scan-vs-loop bitwise equality per method.

Population churn
----------------
Every path accepts an optional ``"active"`` ``[T, M]`` bool mask in the
colocation dict (``repro.mobility``'s churn mask generators build them):
inactive mules neither train nor exchange nor contribute to space
aggregation for that step, on every method and on the distributed engine
alike (the mask ANDs into the delivery mask before the fused psum, so
distributed == single-host under churn). The mask is *data*, not a static:
dense (absent mask == all-ones) and churned runs of the same shape share
one cache entry and one compiled program — zero retraces.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from collections import OrderedDict

from repro.core.population import (PopulationConfig, TrainFn,
                                   make_method_step, population_step)

# LRU-bounded: callers that build fresh batch/eval closures per experiment
# (their identity is part of the key) can never hit, so eviction caps the
# executables + closure-captured datasets such loops would otherwise leak.
_JIT_CACHE: "OrderedDict[Any, Callable]" = OrderedDict()
_JIT_CACHE_MAX = 32
_STATS = {"traces": 0, "hits": 0, "misses": 0}


def jit_cache_stats(per_process: bool = False) -> Dict[str, int]:
    """Snapshot of engine cache counters (traces/hits/misses).

    ``per_process=True`` prefixes every key with ``p{process_index}/`` so
    retrace assertions aggregated across a ``jax.distributed`` cluster
    (each process has its own cache and counters) stay attributable —
    the scale bench merges the dicts from every rank and pins each
    ``p*/retraces``-style delta to zero by name.
    """
    if not per_process:
        return dict(_STATS)
    prefix = f"p{jax.process_index()}/"
    return {prefix + k: v for k, v in _STATS.items()}


def jit_cache_clear() -> None:
    """Drop all memoized replays and reset the counters."""
    _JIT_CACHE.clear()
    for k in _STATS:
        _STATS[k] = 0


# jitted gathers for the between-chunk re-bucket swap: an eager gather on
# an array whose shards span processes is rejected outside jit, and under
# jit the same gather is bitwise-identical on a single process
_take_rows = jax.jit(lambda l, o: jnp.take(l, jnp.asarray(o), axis=0))
_take_cols = jax.jit(lambda l, o: jnp.take(l, jnp.asarray(o), axis=1))


def _sig(tree: Any) -> Any:
    """Hashable shape/dtype signature of a pytree (structure included)."""
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef,) + tuple(
        (tuple(np.shape(l)), np.dtype(jnp.result_type(l)).str) for l in leaves)


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


def _colocation_tensors(colocation, n_steps=None):
    """Normalize a colocation dict to (fid, exch, pos, area, act) arrays.

    ``act`` is the per-step activity (churn) mask ``[T, M]`` bool from the
    ``"active"`` key; absent, it defaults to all-ones — the dense
    population. Because the mask is data (same shape/dtype either way), a
    dense and a churned run of the same schedule shape share one compiled
    replay. Inputs already on device stay on device (no host copy).
    """
    fid = _dev(colocation["fixed_id"], jnp.int32)
    exch = _dev(colocation["exchange"], bool)
    t, m = fid.shape[-2], fid.shape[-1]
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


def _scan_core(state, last, fid, exch, pos, area, act, ts, stacked_batches,
               context, key, *, dynamic: bool, batch_fn, has_context: bool,
               step_fn, eval_every: Optional[int],
               eval_fn: Optional[Callable]):
    """Traceable scan over one contiguous window of the schedule.

    ``ts`` carries the *global* step indices of the window (the streamed
    path hands in ``t0 + arange(chunk)``), so the per-step
    ``fold_in(key, t)`` discipline — and with it bitwise parity against a
    full-horizon replay — is independent of how the horizon is chunked.
    ``last`` enters as carry for the same reason.

    ``area`` is the static [M] vector of the classic contract, or a
    time-varying [T, M] trace (migratory scenarios) — the latter rides the
    scan as one more xs column, so step ``t`` hands the method step its
    *current* row through ``info["area"]``. Returns
    ``(state, last_fid, evals-or-None)``.
    """
    n_steps = fid.shape[0]
    area_dyn = area.ndim == fid.ndim

    def body(carry, xs):
        st, last = carry
        if area_dyn:
            fid_t, exch_t, pos_t, act_t, area_t = xs[:5]
            rest = xs[5:]
        else:
            fid_t, exch_t, pos_t, act_t = xs[:4]
            area_t = area
            rest = xs[4:]
        if dynamic:
            (t,) = rest
            kb, ks = jax.random.split(jax.random.fold_in(key, t))
            bt = (batch_fn(kb, t, context) if has_context
                  else batch_fn(kb, t))
        else:
            t, bt = rest
            ks = jax.random.fold_in(key, t)
        st = step_fn(st, {"fixed_id": fid_t, "exchange": exch_t,
                          "pos": pos_t, "area": area_t, "active": act_t,
                          "t": t}, bt, ks)
        last = jnp.where((fid_t >= 0) & act_t, fid_t, last)
        return (st, last), None

    def xs_slice(lo, hi):
        xs = (fid[lo:hi], exch[lo:hi], pos[lo:hi], act[lo:hi])
        if area_dyn:
            xs = xs + (area[lo:hi],)
        xs = xs + (ts[lo:hi],)
        if not dynamic:
            xs = xs + (jax.tree.map(lambda l: l[lo:hi], stacked_batches),)
        return xs

    carry = (state, last)

    if eval_fn is None or not eval_every:
        carry, _ = jax.lax.scan(body, carry, xs_slice(0, n_steps))
        return carry[0], carry[1], None

    ev = ((lambda st, last: eval_fn(st, last, context)) if has_context
          else eval_fn)
    n_ev = n_steps // eval_every

    def chunk(carry, xs):
        carry, _ = jax.lax.scan(body, carry, xs)
        st, last = carry
        return carry, ev(st, last)

    head = jax.tree.map(
        lambda l: l[: n_ev * eval_every].reshape(
            (n_ev, eval_every) + l.shape[1:]), xs_slice(0, n_steps))
    carry, evals = jax.lax.scan(chunk, carry, head)
    if n_ev * eval_every < n_steps:              # trailing partial chunk
        carry, _ = jax.lax.scan(body, carry,
                                xs_slice(n_ev * eval_every, n_steps))
    return carry[0], carry[1], evals


def _build_replay(batches: Any, train_fn: TrainFn, cfg: PopulationConfig, *,
                  method: str, eval_every: Optional[int],
                  eval_fn: Optional[Callable], n_steps: int,
                  has_context: bool,
                  step_builder: Optional[Callable] = None) -> Callable:
    """Un-jitted replay core ``(state, fid, exch, pos, area, stacked_batches,
    context, key) -> (state, last_fid, evals)`` closed over the statics.

    ``step_builder(area) -> step_fn`` overrides the per-step update (the
    distributed engine injects its shard-local collective step here); the
    default is the single-host ``make_method_step`` dispatch.

    The activity mask rides the scan as one more ``[T, M]`` xs column:
    step ``t`` hands ``act[t]`` to the method step as ``info["active"]``
    and gates ``last_fid`` (a sleeping mule records no visit).
    """
    dynamic = callable(batches)
    batch_fn = batches if dynamic else None
    if step_builder is None:
        step_builder = lambda area: make_method_step(method, train_fn, cfg,
                                                     area)

    def replay(state, fid, exch, pos, area, act, stacked_batches, context,
               key):
        _STATS["traces"] += 1          # python side effect: fires per trace
        step_fn = step_builder(area)
        n_mules = fid.shape[1]
        ts = jnp.arange(n_steps, dtype=jnp.int32)
        last = jnp.zeros((n_mules,), jnp.int32)
        return _scan_core(state, last, fid, exch, pos, area, act, ts,
                          stacked_batches, context, key, dynamic=dynamic,
                          batch_fn=batch_fn, has_context=has_context,
                          step_fn=step_fn, eval_every=eval_every,
                          eval_fn=eval_fn)

    return replay


def _build_chunk_replay(generator, batches: Any, train_fn: TrainFn,
                        cfg: PopulationConfig, *, method: str,
                        eval_every: Optional[int],
                        eval_fn: Optional[Callable], chunk_len: int,
                        has_context: bool,
                        step_builder: Optional[Callable] = None,
                        rebucket: bool = False,
                        pmean_axis: Optional[str] = None) -> Callable:
    """Un-jitted streamed-chunk core ``(state, last, t0, gen_arrays,
    stacked_chunk, context, key) -> (state, last_fid, evals)``.

    The colocation slice is *generated inside the trace*: the generator's
    ``expand`` runs on its array pytree (a traced input — under
    ``shard_map`` each shard holds and expands only its own mule columns)
    at global steps ``t0 .. t0+chunk_len``, feeding the same ``_scan_core``
    the materialized path scans. Only the generator's *static* config is
    closed over, so one compiled program serves every same-shape chunk of
    every same-signature generator, whatever the horizon.

    ``rebucket=True`` compiles the re-bucketing variant: the signature
    grows a ``bucket_area`` input after ``gen_arrays`` (each mule's area at
    the last bucket swap, shard-local under shard_map) and the return grows
    ``(drift, area_last)`` before ``evals`` — the fraction of mules whose
    end-of-chunk area left their bucket (``pmean``'d over ``pmean_axis``
    into a replicated scalar, so the trigger costs one tiny collective per
    chunk) and the end-of-chunk area vector the host driver argsorts into
    the next bucket order when the drift crosses the threshold.
    """
    dynamic = callable(batches)
    batch_fn = batches if dynamic else None
    if step_builder is None:
        step_builder = lambda area: make_method_step(method, train_fn, cfg,
                                                     area)

    def chunk_replay(state, last, t0, gen_arrays, *rest):
        if rebucket:
            bucket_area, stacked_chunk, context, key = rest
        else:
            stacked_chunk, context, key = rest
        _STATS["traces"] += 1          # python side effect: fires per trace
        ts = jnp.asarray(t0, jnp.int32) + jnp.arange(chunk_len,
                                                     dtype=jnp.int32)
        co = generator.expand(gen_arrays, None, t0, chunk_len)
        step_fn = step_builder(co["area"])
        out = _scan_core(state, last, co["fixed_id"], co["exchange"],
                         co["pos"], co["area"], co["active"], ts,
                         stacked_chunk, context, key, dynamic=dynamic,
                         batch_fn=batch_fn, has_context=has_context,
                         step_fn=step_fn, eval_every=eval_every,
                         eval_fn=eval_fn)
        if not rebucket:
            return out
        st, last_fid, evals = out
        area_arr = co["area"]
        area_end = area_arr[-1] if area_arr.ndim == 2 else area_arr
        drift = jnp.mean((area_end != bucket_area).astype(jnp.float32))
        if pmean_axis:
            # ordered, not lax.pmean: the swap decision must be identical
            # on every process/backend or ranks could disagree on whether
            # to reorder (and single- vs multi-process runs would diverge)
            from repro.core.distributed import ordered_pmean
            drift = ordered_pmean(drift, pmean_axis)
        return st, last_fid, drift, jnp.asarray(area_end, jnp.int32), evals

    return chunk_replay


def _distributed_specs(state, batches, dcfg, *, vmapped: bool,
                       area_dyn: bool = False):
    """shard_map in/out PartitionSpecs for the distributed replay.

    Mule-population leaves (leading mule axis) shard over ``dcfg.data_axis``;
    everything else replicates. With ``vmapped`` the seed stack axis is an
    extra unsharded leading dim (the seed vmap sits *inside* the shard_map
    block, outside the mule axis). ``area_dyn`` marks a time-varying
    [T, M] area trace, which shards like the other colocation columns.
    """
    from jax.sharding import PartitionSpec as P
    ax = dcfg.data_axis
    lead = (None,) if vmapped else ()

    def subtree(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    state_specs = {
        k: subtree(v, P(*lead, ax) if k.startswith("mule") else P())
        for k, v in state.items()
    }
    if callable(batches) or batches is None:
        batch_specs = P()                       # no leaves to partition
    else:
        batch_specs = {
            k: subtree(v, P(*lead, None, ax) if k == "mule" else P())
            for k, v in batches.items()
        }
    area_spec = P(*lead, None, ax) if area_dyn else P(*lead, ax)
    in_specs = (state_specs,
                P(*lead, None, ax), P(*lead, None, ax),   # fid, exch
                P(*lead, None, ax), area_spec,            # pos, area
                P(*lead, None, ax),                       # activity mask
                batch_specs, P(), P())                    # batches, ctx, key
    out_specs = (state_specs, P(*lead, ax), P())          # state, last, evals
    return in_specs, out_specs


def get_compiled_replay(state, fid, exch, pos, area, act, batches, context,
                        key, train_fn: TrainFn, cfg: PopulationConfig, *,
                        method: str, eval_every: Optional[int],
                        eval_fn: Optional[Callable],
                        vmapped: bool = False, donate: bool = False,
                        mesh=None, dcfg=None) -> Callable:
    """Fetch (or build + memoize) the jitted replay for this signature.

    ``vmapped=True`` wraps the core in ``jax.vmap`` over a leading stack
    axis on every array argument (``repro.scenarios.sweep`` uses this); the
    leading-axis difference in the shape signature keeps batched and
    unbatched programs in separate cache slots.

    ``mesh``/``dcfg`` select the distributed kind: the (possibly vmapped)
    core is wrapped in ``shard_map`` over the mesh with the step from
    ``make_distributed_method_step``, and both join the cache key.

    ``donate=True`` donates the state pytree (``donate_argnums=(0,)``) so
    the replay reuses its buffers in place — callers must not touch the
    input state afterwards; parity paths that replay the same state twice
    keep the default. Donated and undonated programs cache separately.
    """
    dynamic = callable(batches)
    n_steps = int(fid.shape[-2])
    kind = (("distributed_sweep" if vmapped else "distributed")
            if mesh is not None else ("sweep" if vmapped else "population"))
    cache_key = (
        kind, method, cfg, eval_every,
        n_steps, train_fn, eval_fn, batches if dynamic else None,
        _sig(state), _sig((fid, exch, pos, area, act)),
        None if dynamic else _sig(batches),
        None if context is None else _sig(context), _sig(key),
        donate, None if mesh is None else (mesh, dcfg),
    )
    fn = _JIT_CACHE.get(cache_key)
    if fn is not None:
        _STATS["hits"] += 1
        _JIT_CACHE.move_to_end(cache_key)
        return fn
    _STATS["misses"] += 1
    step_builder = None
    if mesh is not None:
        from repro.core.distributed import make_distributed_method_step
        dist_step = make_distributed_method_step(method, train_fn, dcfg,
                                                 mesh=mesh)
        step_builder = lambda area: dist_step
    core = _build_replay(batches, train_fn, cfg, method=method,
                         eval_every=eval_every, eval_fn=eval_fn,
                         n_steps=n_steps, has_context=context is not None,
                         step_builder=step_builder)
    if vmapped:
        core = jax.vmap(core)
    if mesh is not None:
        in_specs, out_specs = _distributed_specs(
            state, batches, dcfg, vmapped=vmapped,
            area_dyn=np.ndim(area) == np.ndim(fid))
        core = jax.shard_map(core, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    fn = jax.jit(core, donate_argnums=(0,) if donate else ())
    _JIT_CACHE[cache_key] = fn
    while len(_JIT_CACHE) > _JIT_CACHE_MAX:
        _JIT_CACHE.popitem(last=False)
    return fn


def _streamed_specs(state, generator, batches, dcfg, *,
                    rebucket: bool = False):
    """shard_map in/out PartitionSpecs for the streamed chunk replay.

    Argument order mirrors ``_build_chunk_replay``: (state, last, t0,
    gen_arrays[, bucket_area], stacked_chunk, context, key). Mule-population
    leaves and the generator's mule-leading arrays (its ``specs`` method
    knows which) shard over ``dcfg.data_axis``; ``t0``/context/key
    replicate. The re-bucketing variant adds the sharded ``bucket_area``
    input and the ``(drift replicated, area_last sharded)`` outputs.
    """
    from jax.sharding import PartitionSpec as P
    ax = dcfg.data_axis

    def subtree(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    state_specs = {
        k: subtree(v, P(ax) if k.startswith("mule") else P())
        for k, v in state.items()
    }
    if callable(batches) or batches is None:
        batch_specs = P()
    else:
        batch_specs = {
            k: subtree(v, P(None, ax) if k == "mule" else P())
            for k, v in batches.items()
        }
    if rebucket:
        in_specs = (state_specs, P(ax), P(), generator.specs(ax), P(ax),
                    batch_specs, P(), P())
        out_specs = (state_specs, P(ax), P(), P(ax), P())
    else:
        in_specs = (state_specs, P(ax), P(), generator.specs(ax),
                    batch_specs, P(), P())
        out_specs = (state_specs, P(ax), P())
    return in_specs, out_specs


def get_compiled_chunk_replay(state, generator, gen_arrays, batches, context,
                              key, train_fn: TrainFn, cfg: PopulationConfig,
                              *, method: str, eval_every: Optional[int],
                              eval_fn: Optional[Callable], chunk_len: int,
                              stacked_chunk: Any = None, donate: bool = True,
                              mesh=None, dcfg=None,
                              rebucket: bool = False) -> Callable:
    """Fetch (or build + memoize) the jitted streamed-chunk replay.

    The cache key is deliberately **horizon-free**: it hashes the
    generator's *class + static_token() + array signature* and the chunk
    shape, never ``n_steps`` or ``t0`` — so replaying 10^3 or 10^7 steps
    through the same generator family compiles exactly one program per
    distinct chunk length (the tail chunk, when ``n_steps % chunk_len``,
    is the one extra entry). ``donate=True`` (the default here — streaming
    exists for populations too big to copy) donates *state and last_fid*
    (``donate_argnums=(0, 1)``), so the carry ping-pongs through the same
    buffers across the whole chunk loop.
    """
    dynamic = callable(batches)
    kind = ("stream_distributed" if mesh is not None else "stream") \
        + ("_rebucket" if rebucket else "")
    cache_key = (
        kind, method, cfg, eval_every, chunk_len,
        type(generator).__qualname__, generator.static_token(),
        train_fn, eval_fn, batches if dynamic else None,
        _sig(state), _sig(gen_arrays),
        None if dynamic else _sig(stacked_chunk),
        None if context is None else _sig(context), _sig(key),
        donate, None if mesh is None else (mesh, dcfg),
    )
    fn = _JIT_CACHE.get(cache_key)
    if fn is not None:
        _STATS["hits"] += 1
        _JIT_CACHE.move_to_end(cache_key)
        return fn
    _STATS["misses"] += 1
    step_builder = None
    if mesh is not None:
        from repro.core.distributed import make_distributed_method_step
        dist_step = make_distributed_method_step(method, train_fn, dcfg,
                                                 mesh=mesh)
        step_builder = lambda area: dist_step
    core = _build_chunk_replay(generator, batches, train_fn, cfg,
                               method=method, eval_every=eval_every,
                               eval_fn=eval_fn, chunk_len=chunk_len,
                               has_context=context is not None,
                               step_builder=step_builder, rebucket=rebucket,
                               pmean_axis=(dcfg.data_axis
                                           if mesh is not None else None))
    if mesh is not None:
        in_specs, out_specs = _streamed_specs(state, generator, batches,
                                              dcfg, rebucket=rebucket)
        core = jax.shard_map(core, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    fn = jax.jit(core, donate_argnums=(0, 1) if donate else ())
    _JIT_CACHE[cache_key] = fn
    while len(_JIT_CACHE) > _JIT_CACHE_MAX:
        _JIT_CACHE.popitem(last=False)
    return fn


def run_population_streamed(state: Dict[str, Any], generator, batches: Any,
                            train_fn: TrainFn, cfg: PopulationConfig, key, *,
                            n_steps: Optional[int] = None,
                            chunk_len: int = 64,
                            eval_every: Optional[int] = None,
                            eval_fn: Optional[Callable] = None,
                            method: str = "mlmule", context: Any = None,
                            donate: bool = True, mesh=None, dcfg=None
                            ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``run_population`` without the ``[T, M]`` schedule: colocation is
    generated chunk-by-chunk *inside* the compiled replay.

    generator: a chunk generator (``repro.mobility.streaming``) —
               ``compact_colocation(...)`` streams any registered
               scenario's schedule from per-mule RLE segments;
               ``commuter_stream(...)`` is fully procedural (O(M) memory,
               any horizon). Schedule memory is O(chunk_len · M) live
               slices plus the generator's compact arrays, never O(T · M).
    n_steps:   horizon; defaults to ``generator.n_steps``.
    chunk_len: steps generated + scanned per compiled dispatch. Must be a
               multiple of ``eval_every`` when ``eval_fn`` is set (so
               evals land on the same global steps as the materialized
               engine). Bigger chunks amortize dispatch; smaller chunks
               shrink the live schedule slice.
    donate:    default **True** (unlike ``run_population``): state and
               ``last_fid`` buffers are donated each chunk and rebound,
               so the population updates in place for the whole run. Pass
               ``False`` when replaying the same input state again.
    mesh/dcfg: run distributed — the generator expands *shard-locally*
               under ``shard_map`` (each shard computes only its own mule
               columns; no global schedule is ever gathered). ``dcfg`` is
               required with a mesh; ``mesh=None`` with a ``dcfg`` picks
               one like ``run_population_distributed``. ``cfg`` is
               ignored in favor of ``dcfg.pop`` when ``dcfg`` is set.

    Mid-run re-bucketing (``dcfg.rebucket_every > 0``): every
    ``rebucket_every`` steps — which must be a multiple of ``chunk_len``,
    so the check lands on a chunk boundary where ``generator.expand`` gives
    a natural sync point — the compiled chunk emits the psum'd fraction of
    mules whose area drifted off their bucket. Past
    ``dcfg.rebucket_threshold``, the driver argsorts the end-of-chunk area
    into a fresh bucket order and permutes the full live mule state
    (``reorder_mule_state`` — models, timestamps, every ``mule*`` carry),
    the ``last_fid`` column, the generator's in-flight mule columns
    (``reorder_generator_arrays``) and any stacked mule batches, so the
    ring's hop pruning keeps biting as the population migrates.
    ``aux["rebucket"]`` reports ``{checks, swaps, drift, order}`` (``order``
    is the cumulative permutation: entry ``p`` is the original index of the
    mule now in slot ``p`` — apply it to per-mule outputs to recover the
    input ordering). Note a swap renumbers mule slots, so positional batch
    callables and per-mule key draws follow the *slot*, exactly like
    build-time bucketing — a re-bucketed run is the same simulation family
    with mules renamed mid-run, and parity (pruned == full ring, streamed
    == materialized) holds across every swap because the trigger depends
    only on the area schedule, never on pruning or model state.

    Everything else (batches/eval/method/context contracts, the returned
    ``(final_state, aux)``) matches ``run_population`` — and so do the
    results: a streamed replay is bitwise-equal to the materialized engine
    over ``materialize_generator(generator)``, chunk boundaries included
    (the global-step key discipline makes chunking invisible).
    """
    if mesh is not None and dcfg is None:
        raise ValueError("run_population_streamed: mesh requires dcfg")
    if key is None:
        raise TypeError("run_population_streamed() missing required "
                        "argument: 'key'")
    pcfg = dcfg.pop if dcfg is not None else cfg
    n_steps = int(generator.n_steps if n_steps is None else n_steps)
    n_mules = int(generator.n_mules)
    if chunk_len <= 0:
        raise ValueError(f"chunk_len={chunk_len} must be positive")
    if eval_fn is not None and eval_every and chunk_len % eval_every:
        raise ValueError(
            f"chunk_len={chunk_len} must be a multiple of "
            f"eval_every={eval_every} so streamed evals land on the same "
            f"global steps as the materialized engine")
    rb = int(getattr(dcfg, "rebucket_every", 0) or 0) if dcfg is not None \
        else 0
    if rb > 0 and rb % chunk_len:
        raise ValueError(
            f"rebucket_every={rb} must be a multiple of "
            f"chunk_len={chunk_len} so re-bucketing lands on chunk "
            "boundaries (the streamed engine swaps state between chunks)")
    if dcfg is not None:
        dcfg = _resolve_ring_bits(dcfg, getattr(generator, "max_area", 0))
        if mesh is None:
            mesh = _auto_mesh(method, n_mules, dcfg)
        _check_mule_sharding(n_mules, mesh, dcfg)
    gen_arrays = generator.arrays()
    dynamic = callable(batches)
    last = jnp.zeros((n_mules,), jnp.int32)
    evals_chunks = []
    rebucket = rb > 0
    rb_aux = None
    if rebucket:
        from repro.core.distributed import (global_bucket_order,
                                            reorder_mule_state)
        from repro.mobility.streaming import reorder_generator_arrays
        a0 = generator.expand(gen_arrays, None, jnp.asarray(0, jnp.int32),
                              1)["area"]
        bucket_area = jnp.asarray(a0[0] if a0.ndim == 2 else a0, jnp.int32)
        threshold = float(getattr(dcfg, "rebucket_threshold", 0.25))
        rb_aux = {"checks": 0, "swaps": 0, "drift": [],
                  "order": np.arange(n_mules)}
    # under jax.distributed the mesh spans processes: commit every input
    # through the placement helpers (sharded leaves hand the runtime only
    # this process's row block); single-process runs skip all of this
    multiproc = mesh is not None and jax.process_count() > 1
    if multiproc:
        from jax.sharding import PartitionSpec as P
        from repro.launch.multiprocess import (host_replicated, put_global,
                                               put_global_tree)
        in_specs, _ = _streamed_specs(state, generator, batches, dcfg,
                                      rebucket=rebucket)
        ax = dcfg.data_axis
        state = put_global_tree(state, mesh, in_specs[0])
        last = put_global(last, mesh, P(ax))
        gen_arrays = put_global_tree(gen_arrays, mesh, generator.specs(ax))
        key = put_global(key, mesh, P())
        if context is not None:
            context = put_global_tree(
                context, mesh, jax.tree.map(lambda _: P(), context))
        if rebucket:
            bucket_area = put_global(bucket_area, mesh, P(ax))
        batch_specs = in_specs[5] if rebucket else in_specs[4]
    for t0 in range(0, n_steps, chunk_len):
        cl = min(chunk_len, n_steps - t0)
        stacked_chunk = (None if dynamic else
                         jax.tree.map(lambda l: l[t0:t0 + cl], batches))
        t0_dev = jnp.asarray(t0, jnp.int32)
        if multiproc:
            t0_dev = put_global(t0_dev, mesh, P())
            if stacked_chunk is not None:
                stacked_chunk = put_global_tree(stacked_chunk, mesh,
                                                batch_specs)
        fn = get_compiled_chunk_replay(
            state, generator, gen_arrays, batches, context, key, train_fn,
            pcfg, method=method, eval_every=eval_every, eval_fn=eval_fn,
            chunk_len=cl, stacked_chunk=stacked_chunk, donate=donate,
            mesh=mesh, dcfg=dcfg, rebucket=rebucket)
        if rebucket:
            state, last, drift, area_last, ev = fn(
                state, last, t0_dev, gen_arrays,
                bucket_area, stacked_chunk, context, key)
        else:
            state, last, ev = fn(state, last, t0_dev,
                                 gen_arrays, stacked_chunk, context, key)
        if ev is not None:
            evals_chunks.append(ev)
        t_end = t0 + cl
        if rebucket and t_end % rb == 0 and t_end < n_steps:
            rb_aux["checks"] += 1
            # drift is replicated; multi-process arrays span devices that
            # np.asarray refuses, so read this process's replica
            d = float(drift) if not multiproc else \
                float(host_replicated(drift))
            rb_aux["drift"].append(d)
            if d > threshold:
                # the bucket order comes out of a compiled exact-int psum
                # + replicated stable argsort (multi-host safe: the [M]
                # area vector is sharded across processes, so no single
                # host could np.argsort it) — bitwise the same decision
                # as the former host-side np.argsort(kind="stable")
                order_r, area_r = global_bucket_order(
                    area_last, mesh, dcfg.data_axis)
                if multiproc:
                    order = host_replicated(order_r)
                    area_now = host_replicated(area_r)
                else:
                    order = np.asarray(order_r)
                    area_now = np.asarray(area_r)
                if not np.array_equal(order, np.arange(n_mules)):
                    state = reorder_mule_state(state, order)
                    last = _take_rows(last, order)
                    gen_arrays = reorder_generator_arrays(
                        generator, gen_arrays, order)
                    if not dynamic:
                        batches = {
                            k: (jax.tree.map(
                                lambda l: _take_cols(l, order), v)
                                if k == "mule" else v)
                            for k, v in batches.items()}
                    rb_aux["order"] = rb_aux["order"][order]
                    rb_aux["swaps"] += 1
                # the current area in the (possibly) new layout is the
                # baseline the next drift check measures against
                bucket_area = jnp.asarray(area_now[order], jnp.int32)
                if multiproc:
                    bucket_area = put_global(bucket_area, mesh, P(ax))
    n_ev = n_steps // eval_every if (eval_fn is not None and eval_every) else 0
    steps = (np.arange(n_ev) + 1) * eval_every - 1 if n_ev else \
        np.zeros((0,), int)
    evals = None
    if evals_chunks:
        evals = (evals_chunks[0] if len(evals_chunks) == 1 else
                 jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                              *evals_chunks))
    aux = {"last_fid": last, "eval_steps": steps, "evals": evals}
    if rb_aux is not None:
        aux["rebucket"] = rb_aux
    return state, aux


def run_population(state: Dict[str, Any], colocation: Dict[str, Any],
                   batches: Any, train_fn: TrainFn, cfg: PopulationConfig,
                   key, *, eval_every: Optional[int] = None,
                   eval_fn: Optional[Callable] = None,
                   method: str = "mlmule", context: Any = None,
                   donate: bool = False
                   ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Scan one method over a precomputed co-location schedule (jit-cached).

    state:      population state from ``init_population``.
    colocation: {"fixed_id": [T, M] int32 (-1 = corridor),
                 "exchange": [T, M] bool}; the peer-encounter methods also
                 read "pos" [T, M, 2] and "area" [M] (zero-filled when
                 absent; extra keys ignored). An optional "active" [T, M]
                 bool churn mask switches mules off per step: inactive
                 mules neither train, nor exchange, nor count toward space
                 aggregation, and record no ``last_fid`` visit (all-ones ==
                 the dense population, bitwise — same compiled program,
                 the mask is data).
    batches:    callable ``(key, t[, context]) -> {"fixed": ..., "mule":
                ...}`` sampled inside the scan (traceable), or a pytree of
                stacked ``[T, ...]`` leaves consumed as scan inputs.
    method:     any of ``METHODS_MOBILE`` (see ``make_method_step``).
    context:    optional pytree passed through to ``batches`` and
                ``eval_fn`` as a trailing argument — the hook for per-call
                (or, under ``run_sweep``, per-seed) datasets.
    eval_fn:    optional traceable ``(state, last_fid [M][, context]) ->
                metric pytree`` run inside the scan every ``eval_every``
                steps (``last_fid`` is each mule's most recent fixed
                device, 0 before any visit).
    donate:     donate the state buffers to the compiled replay (in-place
                update for large populations). The input ``state`` arrays
                are dead after the call — leave False when replaying the
                same state again (parity tests do).

    Returns ``(final_state, aux)`` with
    ``aux = {"last_fid": [M], "eval_steps": np [E], "evals": stacked/None}``
    where eval step ``i`` is taken after step ``(i+1)*eval_every - 1``.
    """
    fid, exch, pos, area, act = _colocation_tensors(colocation)
    n_steps = fid.shape[0]
    stacked = None if callable(batches) else batches
    fn = get_compiled_replay(state, fid, exch, pos, area, act, batches,
                             context, key, train_fn, cfg, method=method,
                             eval_every=eval_every, eval_fn=eval_fn,
                             donate=donate)
    state, last, evals = fn(state, fid, exch, pos, area, act, stacked,
                            context, key)
    n_ev = n_steps // eval_every if (eval_fn is not None and eval_every) else 0
    steps = (np.arange(n_ev) + 1) * eval_every - 1 if n_ev else \
        np.zeros((0,), int)
    return state, {"last_fid": last, "eval_steps": steps, "evals": evals}


def run_population_loop(state: Dict[str, Any], colocation: Dict[str, Any],
                        batches: Any, train_fn: TrainFn,
                        cfg: PopulationConfig, key, *,
                        method: str = "mlmule", context: Any = None
                        ) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """The retired per-step harness driver, kept as the parity reference.

    One jitted dispatch per simulation step with Python-level method
    branching — exactly the loop ``benchmarks/common.py`` ran before every
    method moved onto the scan. Parity tests pin ``run_population`` to this
    bitwise at fixed seed; ``benchmarks/engine_micro.py`` times the gap.

    ``context`` mirrors the scan path's hook: when set (and ``batches`` is
    a callable) the loop calls ``batches(kb, t, context)``, so parity tests
    cover context-carrying runs too.

    Churn: a colocation ``"active"`` mask replays with the same per-step
    Python dispatch — inactive mules skip training/exchange and keep their
    models via ``apply_activity_mask``, mirroring the scan's masked method
    steps operation for operation. Without the key the loop is the
    pre-mask driver verbatim.

    Returns ``(final_state, last_fid)``.
    """
    from repro.baselines import gossip_step, local_step, oppcl_step
    from repro.core.population import apply_activity_mask

    step = jax.jit(lambda s, i, b, k: population_step(s, i, b, train_fn,
                                                      cfg, k))
    jit_local = jax.jit(lambda m, b, k: local_step(m, b, train_fn, k))
    jit_gossip = jax.jit(
        lambda m, p, a, b, k, act: gossip_step(m, p, a, b, train_fn, k,
                                               active=act,
                                               backend=cfg.enc_backend))
    jit_oppcl = jax.jit(
        lambda m, p, a, b, k, act: oppcl_step(m, p, a, b, train_fn, k,
                                              active=act))
    mask_sel = jax.jit(apply_activity_mask)

    fid_T, exch_T, pos_T, area_A, act_T = _colocation_tensors(colocation)
    area_dyn = area_A.ndim == 2
    masked = "active" in colocation and colocation["active"] is not None
    n_steps, n_mules = fid_T.shape
    dynamic = callable(batches)
    state = dict(state)
    last_fid = jnp.zeros((n_mules,), jnp.int32)
    for t in range(n_steps):
        fid, exch, pos = fid_T[t], exch_T[t], pos_T[t]
        area = area_A[t] if area_dyn else area_A
        act = act_T[t] if masked else None
        if dynamic:
            kb, ks = jax.random.split(jax.random.fold_in(key, t))
            bt = batches(kb, t, context) if context is not None else \
                batches(kb, t)
        else:
            ks = jax.random.fold_in(key, t)
            bt = jax.tree.map(lambda l: l[t], batches)
        present = (fid >= 0) if act is None else ((fid >= 0) & act)
        last_fid = jnp.where(present, fid, last_fid)
        info = {"fixed_id": fid, "exchange": exch}
        if act is not None:
            info["active"] = act
        if method == "mlmule":
            state = step(state, info, bt, ks)
        elif method == "local":
            side = "fixed_models" if cfg.mode == "fixed" else "mule_models"
            trained = jit_local(
                state[side], bt["fixed" if cfg.mode == "fixed" else "mule"],
                ks)
            if side == "mule_models":
                trained = mask_sel(act, trained, state[side])
            state[side] = trained
        elif method == "gossip":
            # peer exchange also costs 3 time steps (paper Sec 4.3.1)
            if t % 3 == 2:
                new = jit_gossip(state["mule_models"], pos, area, bt["mule"],
                                 ks, act)
                state["mule_models"] = mask_sel(act, new,
                                                state["mule_models"])
        elif method == "oppcl":
            if t % 3 == 2:
                new = jit_oppcl(state["mule_models"], pos, area, bt["mule"],
                                ks, act)
                state["mule_models"] = mask_sel(act, new,
                                                state["mule_models"])
        elif method == "mlmule+gossip":
            state = step(state, info, bt, ks)
            if t % 3 == 2:
                kg = jax.random.fold_in(ks, 1)
                new = jit_gossip(state["mule_models"], pos, area, bt["mule"],
                                 kg, act)
                state["mule_models"] = mask_sel(act, new,
                                                state["mule_models"])
        else:
            raise ValueError(method)
    return state, last_fid


# ---------------------------------------------------------------------------
# distributed replay: the scan under shard_map over the mule axis
# ---------------------------------------------------------------------------


def _resolve_ring_bits(dcfg, max_area):
    """Pick the ring predicate width when ``dcfg.ring_bits == 0`` (auto).

    Widens to 64 bits once any area id reaches 32 — a 32-wide mask folds
    areas ``% 32``, aliasing distinct areas onto one bit so the ring
    quietly stops pruning. Safe to resolve per-run: pruning is exact, so
    the mask width never changes results, only the prune rate (and the
    jit cache key, which hashes the resolved config by value).
    """
    import dataclasses
    if getattr(dcfg, "ring_bits", 0):
        return dcfg
    return dataclasses.replace(dcfg,
                               ring_bits=64 if int(max_area) >= 32 else 32)


def _check_mule_sharding(n_mules: int, mesh, dcfg) -> None:
    shards = mesh.shape[dcfg.data_axis]
    if n_mules % shards:
        raise ValueError(
            f"n_mules={n_mules} must divide evenly over the "
            f"{dcfg.data_axis!r} mesh axis (size {shards})")


def _auto_mesh(method: str, n_mules: int, dcfg):
    """Mesh for ``run_population_distributed(mesh=None)``.

    Consults ``suggest_mesh_shape`` — the roofline-ranked (pod, data)
    shape from the committed ``BENCH_roofline.json`` mesh rows — the way
    the kernels consult ``tuned_block_d``; a suggestion that doesn't fit
    this process (too few devices, a data size that doesn't divide
    ``n_mules``, a pod axis the dcfg doesn't carry) falls back, like an
    absent cache, to the largest single-pod data axis the local devices
    allow.
    """
    import jax
    from repro.launch.autotune import suggest_mesh_shape
    from repro.launch.mesh import make_mule_mesh

    n_dev = jax.device_count()
    shape = suggest_mesh_shape(method, n_mules)
    if shape is not None:
        pod, data = shape
        if (pod * data <= n_dev and data and n_mules % data == 0
                and (dcfg.pod_axis or pod == 1)):
            return make_mule_mesh(pod, data, pod_axis=dcfg.pod_axis,
                                  data_axis=dcfg.data_axis)
    data = max(d for d in range(1, n_dev + 1) if n_mules % d == 0)
    return make_mule_mesh(1, data, pod_axis=dcfg.pod_axis,
                          data_axis=dcfg.data_axis)


def run_population_distributed(state: Dict[str, Any],
                               colocation: Dict[str, Any], batches: Any,
                               train_fn: TrainFn, dcfg, mesh=None, key=None, *,
                               eval_every: Optional[int] = None,
                               eval_fn: Optional[Callable] = None,
                               method: str = "mlmule", context: Any = None,
                               donate: bool = False
                               ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``run_population`` with the population sharded over the mesh.

    The whole replay — the ``psum`` collective schedule of
    ``make_distributed_method_step`` included — is one ``lax.scan`` under
    ``shard_map`` over ``dcfg.data_axis`` (jit-cached like the single-host
    path; the mesh and ``dcfg`` join the cache key). Mule state/colocation
    columns shard, fixed-device state and the freshness sketch replicate.

    state:   ``to_distributed_state(init_population(...), dcfg)`` layout.
    dcfg:    ``repro.core.distributed.DistributedConfig`` — collective
             schedule (``cross_pod``) and axis names; the freshness
             statistic comes from ``dcfg.pop.freshness.stat``.
    mesh:    a ``jax.sharding.Mesh`` whose axes include ``dcfg.data_axis``
             (and ``dcfg.pod_axis`` when set). ``n_mules`` must divide the
             data-axis size. ``None`` picks a shape automatically: the
             roofline-ranked suggestion from the committed
             ``BENCH_roofline.json`` mesh rows (``suggest_mesh_shape``,
             consulted the way the kernels consult ``tuned_block_d``),
             falling back to the widest fitting single-pod data axis.
    batches: the ``run_population`` contract; a batch callable runs inside
             every shard on the replicated key, so it must be
             deterministic in ``(key, t[, context])``; full ``[n_mules,
             ...]`` mule batches are sliced per shard by the step. Stacked
             pytrees shard their ``"mule"`` leaves.
    eval_fn: runs shard-local with replicated outputs assumed — read
             replicated state (``fixed_models``) / replicated context only.
    method:  any of ``METHODS_MOBILE``. The peer-encounter baselines
             (gossip/oppcl/mlmule+gossip) cross shards via the method
             table's ring ``ppermute`` exchange and are bitwise-equal to
             single host on a 1-device mesh under the default
             ``enc_backend="ref"`` (the ring always runs the ref block
             math — a single-host run on the Pallas backend agrees to
             kernel tolerance instead); blockwise accumulation order
             makes multi-shard gossip agree to float tolerance, while
             oppcl's peer pick is order-independent and stays bitwise.
    donate:  donate state buffers (in-place replay); input state is dead
             after the call.

    Returns ``(final_state, aux)`` exactly like ``run_population``.
    """
    if key is None:
        raise TypeError("run_population_distributed() missing required "
                        "argument: 'key'")
    fid, exch, pos, area, act = _colocation_tensors(colocation)
    n_steps = fid.shape[0]
    dcfg = _resolve_ring_bits(dcfg, jnp.max(area) if area.size else 0)
    rb = int(getattr(dcfg, "rebucket_every", 0) or 0)
    if rb > 0:
        # Re-bucketing swaps live state between chunks, so the materialized
        # run delegates to the streamed engine with one chunk per rebucket
        # window — streamed == materialized is pinned bitwise, so this is
        # the same replay with swap points inserted.
        if eval_fn is not None and eval_every and rb % eval_every:
            raise ValueError(
                f"rebucket_every={rb} must be a multiple of "
                f"eval_every={eval_every} so drift checks land on eval "
                "boundaries")
        from repro.mobility.streaming import compact_colocation
        return run_population_streamed(
            state, compact_colocation(colocation), batches, train_fn,
            dcfg.pop, key, n_steps=n_steps, chunk_len=rb,
            eval_every=eval_every, eval_fn=eval_fn, method=method,
            context=context, donate=donate, mesh=mesh, dcfg=dcfg)
    if mesh is None:
        mesh = _auto_mesh(method, fid.shape[1], dcfg)
    _check_mule_sharding(fid.shape[1], mesh, dcfg)
    stacked = None if callable(batches) else batches
    # commit every input to the mesh explicitly: each device holds its
    # shard of the mule columns (and, across processes, each process
    # materializes only its own shards)
    from jax.sharding import PartitionSpec as P
    from repro.launch.multiprocess import put_global, put_global_tree
    in_specs, _ = _distributed_specs(state, batches, dcfg, vmapped=False,
                                     area_dyn=area.ndim == 2)
    state = put_global_tree(state, mesh, in_specs[0])
    fid, exch, pos, area, act = (
        put_global(x, mesh, s) for x, s in
        zip((fid, exch, pos, area, act), in_specs[1:6]))
    if stacked is not None:
        stacked = put_global_tree(stacked, mesh, in_specs[6])
    if context is not None:
        context = put_global_tree(
            context, mesh, jax.tree.map(lambda _: P(), context))
    key = put_global(key, mesh, P())
    fn = get_compiled_replay(state, fid, exch, pos, area, act, batches,
                             context, key, train_fn, dcfg.pop, method=method,
                             eval_every=eval_every, eval_fn=eval_fn,
                             donate=donate, mesh=mesh, dcfg=dcfg)
    state, last, evals = fn(state, fid, exch, pos, area, act, stacked,
                            context, key)
    n_ev = n_steps // eval_every if (eval_fn is not None and eval_every) else 0
    steps = (np.arange(n_ev) + 1) * eval_every - 1 if n_ev else \
        np.zeros((0,), int)
    return state, {"last_fid": last, "eval_steps": steps, "evals": evals}


def run_population_distributed_loop(state: Dict[str, Any],
                                    colocation: Dict[str, Any], batches: Any,
                                    train_fn: TrainFn, dcfg, mesh, key, *,
                                    method: str = "mlmule",
                                    context: Any = None
                                    ) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """Per-step distributed driver: the parity/bench reference.

    One jitted ``shard_map`` dispatch per simulation step — the dispatch
    pattern the deleted dense per-step engine imposed on every
    experiment, now driven through the same method-table step function
    and key discipline as the scan (the fused ``encounter_mix`` schedule
    is the only distributed encounter path), so
    ``run_population_distributed`` is pinned to it bitwise and the bench
    gap between the two is purely the dispatch tax. The jitted step is
    memoized in the engine jit cache, so repeat replays of the same
    signature dispatch warm.

    Returns ``(final_state, last_fid)`` (``last_fid`` sharded like the
    mule axis).
    """
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import make_distributed_method_step

    fid_T, exch_T, pos_T, area_A, act_T = _colocation_tensors(colocation)
    area_dyn = area_A.ndim == 2
    n_steps, n_mules = fid_T.shape
    _check_mule_sharding(n_mules, mesh, dcfg)
    ax = dcfg.data_axis
    state_specs = {
        k: jax.tree.map(lambda _: P(ax) if k in ("mule_models", "mule_ts")
                        else P(), v)
        for k, v in state.items()
    }
    info_specs = {"fixed_id": P(ax), "exchange": P(ax), "pos": P(ax),
                  "area": P(ax), "active": P(ax), "t": P()}
    cache_key = ("dist_loop_step", method, dcfg, mesh, train_fn,
                 _sig(state), area_dyn)
    step = _JIT_CACHE.get(cache_key)
    if step is None:
        _STATS["misses"] += 1
        step_core = make_distributed_method_step(method, train_fn, dcfg,
                                                 mesh=mesh)

        def counted(st, info, bt, k):
            _STATS["traces"] += 1      # python side effect: fires per trace
            return step_core(st, info, bt, k)

        step = jax.jit(jax.shard_map(
            counted, mesh=mesh,
            in_specs=(state_specs, info_specs, P(), P()),
            out_specs=state_specs, check_vma=False))
        _JIT_CACHE[cache_key] = step
        while len(_JIT_CACHE) > _JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)
    else:
        _STATS["hits"] += 1
        _JIT_CACHE.move_to_end(cache_key)

    dynamic = callable(batches)
    last_fid = jnp.zeros((n_mules,), jnp.int32)
    for t in range(n_steps):
        fid, exch, pos, act = fid_T[t], exch_T[t], pos_T[t], act_T[t]
        if dynamic:
            kb, ks = jax.random.split(jax.random.fold_in(key, t))
            bt = batches(kb, t, context) if context is not None else \
                batches(kb, t)
        else:
            ks = jax.random.fold_in(key, t)
            bt = jax.tree.map(lambda l: l[t], batches)
        info = {"fixed_id": fid, "exchange": exch, "pos": pos,
                "area": area_A[t] if area_dyn else area_A,
                "active": act, "t": jnp.asarray(t, jnp.int32)}
        state = step(state, info, bt, ks)
        last_fid = jnp.where((fid >= 0) & act, fid, last_fid)
    return state, last_fid
