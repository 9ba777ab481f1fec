"""Batched sweeps: one vmapped compiled replay per (method, shape) cell.

The paper's headline results (Figs 6-9, Table 1) are seed-averaged curves
across five methods. Running them as S x 5 independent ``run_population``
calls pays Python dispatch and (without the jit cache) a retrace per cell;
``run_sweep`` instead runs the engine's one chunk replay ``vmap``ped over a
stacked seed axis, as one chunk of the whole horizon over the stacked
schedule (``dense_colocation``), so the whole seed batch is ONE XLA program
executed once — the same amortize-across-clients lever FedAvg-style
simulators use.

Batching rules:

- **Seeds vmap.** Everything seed-dependent is stacked on a leading ``[S]``
  axis: population states, colocation tensors, PRNG keys, the optional
  ``context`` pytree (per-seed datasets), and stacked-batch leaves
  (``[S, T, ...]``). ``stack_trees`` builds these stacks.
- **Methods loop.** Two methods can only share a vmapped program when
  their step pytrees AND step computations coincide; the five
  ``METHODS_MOBILE`` all differ in computation (different update rules,
  conditional cadences), so methods run as separate compiled programs.
  The engine's jit cache still amortizes them: each method compiles once
  per shape signature for the life of the process, and the vmapped seed
  batch rides inside each.

Bitwise guarantee (pinned by ``tests/test_sweep.py``): lane ``i`` of a
vmapped sweep equals the ``i``-th sequential ``run_population`` call — the
engine's fold_in/split key discipline is elementwise, and XLA's batched
lowering preserves per-lane numerics on CPU.

``run_sweep_distributed`` composes the seed axis with the distributed
engine's mesh: the seed ``vmap`` sits *inside* the ``shard_map`` block —
stacked outside the sharded mule axis, unsharded — so a distributed
multi-seed sweep is still one program per method, and each lane is
bitwise-equal to a sequential ``run_population_distributed`` call on the
same mesh (``tests/test_distributed.py`` pins it). All five
``METHODS_MOBILE`` sweep distributed: the peer-encounter baselines' ring
``ppermute`` exchange batches under the seed vmap like any other
collective (``tests/test_distributed_engine.py`` pins a gossip lane). The
lanes share one mule layout, so a sweep does not re-bucket
(``DistributedConfig.rebucket_every`` is not read here).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax

from repro.core.population import PopulationConfig, TrainFn
from repro.mobility.streaming import dense_colocation
from repro.scenarios.engine import _replay

SweepResult = Tuple[Dict[str, Any], Dict[str, Any]]


def stack_trees(trees: Sequence[Any]) -> Any:
    """Stack a list of same-structure pytrees along a new leading axis."""
    import jax.numpy as jnp
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def stack_colocations(cos: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Stack per-seed colocation dicts into [S, T, M] engine tensors.

    The churn mask stacks too (``active`` [S, T, M]); seeds without one
    stack as all-ones lanes, so dense and churned seeds can share a sweep.
    """
    return stack_trees([dense_colocation(co).arrays() for co in cos])


def run_sweep(states: Dict[str, Any], colocations: Dict[str, Any],
              batches: Any, train_fn: TrainFn, cfg: PopulationConfig,
              keys, *, eval_every: Optional[int] = None,
              eval_fn: Optional[Callable] = None,
              methods: Union[str, Sequence[str]] = "mlmule",
              context: Any = None, mesh=None, dcfg=None,
              donate: bool = False
              ) -> Union[SweepResult, Dict[str, SweepResult]]:
    """Replay S seeds (x several methods) as vmapped compiled scans.

    states:      population states stacked ``[S, ...]`` (``stack_trees``
                 over per-seed ``init_population`` results).
    colocations: colocation dict with ``[S, T, M]`` tensors
                 (``stack_colocations``), or a single unstacked ``[T, M]``
                 dict shared by every seed (broadcast here). A per-seed
                 ``"active"`` churn mask vmaps with the rest (absent ==
                 dense).
    batches:     traceable callable ``(key, t[, context]) -> batch dict``
                 (shared code; per-seed data goes through ``context``), or
                 a pytree of ``[S, T, ...]`` stacked leaves.
    keys:        stacked PRNG keys ``[S, 2]``.
    context:     optional pytree stacked ``[S, ...]`` handed to ``batches``
                 / ``eval_fn`` as a trailing arg — per-seed datasets.
    methods:     one method name or a sequence of them.
    mesh/dcfg:   distributed mode (``run_sweep_distributed`` fills these):
                 each lane replays on the mule-sharded engine.
    donate:      donate the stacked state buffers (single method only —
                 a second method would replay already-donated state).

    Returns ``(final_states, aux)`` with every array carrying a leading
    ``[S]`` axis (``aux["evals"]`` is ``[S, E, ...]``); for a sequence of
    methods, a ``{method: (final_states, aux)}`` dict.
    """
    import jax.numpy as jnp
    if donate and not isinstance(methods, str):
        raise ValueError("donate=True replays would reuse donated state "
                         "across methods; pass a single method")
    gen = dense_colocation(colocations)
    if gen.arrays()["fixed_id"].ndim == 2:       # shared schedule -> broadcast
        s = jax.tree.leaves(keys)[0].shape[0]
        gen = dense_colocation(jax.tree.map(
            lambda l: jnp.broadcast_to(l, (s,) + l.shape), gen.arrays()))

    def one(method: str) -> SweepResult:
        return _replay(states, gen, batches, train_fn, cfg, keys,
                       n_steps=gen.n_steps, chunk_len=gen.n_steps,
                       eval_every=eval_every, eval_fn=eval_fn, method=method,
                       context=context, donate=donate, mesh=mesh,
                       dcfg=dcfg if mesh is not None else None, vmapped=True)

    if isinstance(methods, str):
        return one(methods)
    return {m: one(m) for m in methods}


def run_sweep_distributed(states: Dict[str, Any], colocations: Dict[str, Any],
                          batches: Any, train_fn: TrainFn, dcfg, mesh,
                          keys, *, eval_every: Optional[int] = None,
                          eval_fn: Optional[Callable] = None,
                          methods: Union[str, Sequence[str]] = "mlmule",
                          context: Any = None, donate: bool = False
                          ) -> Union[SweepResult, Dict[str, SweepResult]]:
    """``run_sweep`` on the mule-sharded distributed engine.

    Same stacking contract as ``run_sweep`` (leading ``[S]`` seed axis on
    states/colocations/keys/context), plus ``dcfg``/``mesh`` from
    ``run_population_distributed``; states follow the
    ``to_distributed_state`` layout, stacked. The seed axis vmaps *inside*
    the ``shard_map`` block (unsharded, outside the mule axis), so the
    whole distributed sweep is one compiled program per method and lane
    ``i`` is bitwise-equal to the ``i``-th sequential
    ``run_population_distributed`` call. ``methods`` accepts any of the
    five ``METHODS_MOBILE`` — the peer-encounter baselines ride their ring
    exchange inside the vmapped scan.
    """
    return run_sweep(states, colocations, batches, train_fn, dcfg.pop, keys,
                     eval_every=eval_every, eval_fn=eval_fn,
                     methods=methods, context=context, mesh=mesh, dcfg=dcfg,
                     donate=donate)
