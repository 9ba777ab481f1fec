"""Shared simulation harness for the paper's experiments.

One entry point, ``run_experiment``, reproduces (at configurable scale):
- Table 1  — fixed-device training, CIFAR-like, {IID, Dir(a)} x methods
- Fig 6/7  — mobile-device training, CIFAR-like Shards, vs Gossip/OppCL/Local
- Fig 8/9  — mobile-device training, IMU HAR
under the random-walk mobility model (P_cross) or synthetic 4Q traces.

Reduced sizes by default (CPU container); ``scale='paper'`` approaches the
paper's 20-mule / 8-fixed / 2500-image setup.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines import CFLState, cfl_round, fedas_round, fedavg_round
from repro.baselines.cfl import cfl_client_models
from repro.configs.mule_cnn import CNNConfig
from repro.configs.mule_lstm_cnn import LSTMCNNConfig
from repro.core import METHODS_MOBILE, PopulationConfig, init_population
from repro.core.freshness import FreshnessConfig
from repro.data import (dirichlet_partition, iid_partition, make_image_dataset,
                        make_imu_dataset, shards_partition)
from repro.data.partition import train_test_split
from repro.mobility import compact_colocation, synth_foursquare_trace
from repro.models.cnn import (accuracy, cnn_forward, init_cnn, init_lstm_cnn,
                              lstm_cnn_forward, xent_loss)
from repro.scenarios import (get_scenario, run_population,
                             run_population_streamed, run_sweep,
                             scenario_generator, stack_colocations,
                             stack_trees, trace_colocation, walk_colocation)

METHODS_FIXED = ("mlmule", "fedavg", "cfl", "fedas", "local")


@dataclasses.dataclass
class ExperimentConfig:
    task: str = "image"            # image | har
    mode: str = "fixed"            # fixed | mobile
    method: str = "mlmule"
    dist: str = "dir0.01"          # iid | dir<alpha> | shards
    pattern: str = "0.1"           # P_cross value as str, or "4q"
    steps: int = 300
    eval_every: int = 50
    n_mules: int = 12
    n_fixed: int = 8
    batch: int = 16
    lr: float = 0.05
    seed: int = 0
    image_size: int = 16
    n_super: int = 20
    n_sub: int = 5
    n_per_sub: int = 16
    noise: float = 3.0
    train_per_device: int = 32   # local-overfitting regime (paper operating point)
    post_local_epochs: int = 1     # Table 1 "Post-Local" fine-tune
    pretrain_steps: int = 120      # per-device local pretraining to the
                                   # paper's 'accuracy stops improving' point
    freshness_off: bool = False    # ablation: disable the staleness filter
    gamma: float = 0.3
    scenario: str = ""             # registry scenario name; overrides
                                   # mode/dist/task/pattern when set
    distributed: bool = False      # replay on the mule-sharded engine over
                                   # the available devices (all methods)
    stream: bool = False           # generate colocation chunk-by-chunk
                                   # inside the compiled replay (O(chunk·M)
                                   # schedule memory) instead of scanning
                                   # the materialized [T, M] tensors;
                                   # results are bitwise-identical
    stream_chunk: int = 0          # steps per streamed chunk (0 = auto:
                                   # eval_every when evals run, else 64)
    rebucket_every: int = 0        # distributed runs: drift-check cadence of
                                   # mid-run re-bucketing (0 = off; must be a
                                   # multiple of the streamed chunk length)
    rebucket_threshold: float = 0.25   # drift fraction that triggers a swap


# ---------------------------------------------------------------------------
# data assembly
# ---------------------------------------------------------------------------


def _pad_to(idx_list: List[np.ndarray], rng) -> np.ndarray:
    n = max(len(i) for i in idx_list)
    out = []
    for i in idx_list:
        if len(i) < n:
            i = np.concatenate([i, rng.choice(i, n - len(i))])
        out.append(i)
    return np.stack(out)


def _image_data_fixed(cfg: ExperimentConfig):
    """Per-fixed-device train/test arrays for the Table-1 setting."""
    x, sup, sub = make_image_dataset(cfg.seed, cfg.n_per_sub, cfg.n_super,
                                     cfg.n_sub, cfg.image_size, cfg.noise)
    rng = np.random.default_rng(cfg.seed)
    if cfg.dist == "iid":
        parts = iid_partition(sup, cfg.n_fixed, cfg.seed)
    elif cfg.dist.startswith("dir"):
        parts = dirichlet_partition(sup, cfg.n_fixed, float(cfg.dist[3:]),
                                    cfg.seed, min_per_part=24)
    elif cfg.dist == "shards":
        n_areas = max(-(-cfg.n_fixed // 4), 2)     # ceil, 4 spaces per area
        sh = shards_partition(sup, sub, n_areas=n_areas, seed=cfg.seed)
        parts = [np.concatenate([sh["space_idx"][(a, s)],
                                 sh["general_idx"][(a, s)]])
                 for a in range(n_areas) for s in range(4)]
    else:
        raise ValueError(cfg.dist)
    tr, te = zip(*[train_test_split(p, 0.2, cfg.seed) for p in parts])
    tr = [t[: cfg.train_per_device] for t in tr]
    tr, te = _pad_to(list(tr), rng), _pad_to(list(te), rng)
    return (jnp.asarray(x[tr]), jnp.asarray(sup[tr]),
            jnp.asarray(x[te]), jnp.asarray(sup[te]))


def _image_data_mobile(cfg: ExperimentConfig, mule_space: np.ndarray,
                       mule_area: np.ndarray):
    """Shards data on mules per Sec 4.3.1: space's sub-class + 5th sub-class."""
    x, sup, sub = make_image_dataset(cfg.seed, cfg.n_per_sub, cfg.n_super,
                                     cfg.n_sub, cfg.image_size, cfg.noise)
    # ceil so every place id's area (place // 4) has a partition, min 2 to
    # keep the pre-registry hardcoded layout for small populations
    n_areas = max(-(-cfg.n_fixed // 4), 2)
    sh = shards_partition(sup, sub, n_areas=n_areas, seed=cfg.seed)
    rng = np.random.default_rng(cfg.seed + 1)
    tr_list = []
    for m in range(cfg.n_mules):
        key = (int(mule_area[m]), int(mule_space[m]))
        local = sh["space_idx"][key]
        general = sh["general_idx"][key]
        cap = max(cfg.train_per_device // 2, 8)
        take = rng.choice(local, min(len(local), cap), replace=False)
        takeg = rng.choice(general, min(len(general), cap), replace=False)
        tr_list.append(np.concatenate([take, takeg]))
    tr = _pad_to(tr_list, rng)
    # per-space test sets (mule evaluated on its current space's data)
    te_idx = _pad_to([sh["space_idx"][(a, s)] for a in range(n_areas)
                      for s in range(4)], rng)
    return (jnp.asarray(x[tr]), jnp.asarray(sup[tr]),
            jnp.asarray(x[te_idx]), jnp.asarray(sup[te_idx]))


def _har_data_mobile(cfg: ExperimentConfig, mule_space, mule_area):
    """IMU data per location; spaces map to EgoExo4D-like locations."""
    x, y, loc = make_imu_dataset(cfg.seed, n_per_cell=cfg.n_per_sub)
    rng = np.random.default_rng(cfg.seed + 2)
    space_loc = rng.permutation(8)          # each space -> a location
    tr_list = []
    for m in range(cfg.n_mules):
        sl = space_loc[int(mule_area[m]) * 4 + int(mule_space[m])]
        idx = np.where(loc == sl)[0]
        tr_list.append(rng.choice(idx, min(len(idx), 120), replace=False))
    tr = _pad_to(tr_list, rng)
    te_idx = _pad_to([np.where(loc == space_loc[f])[0][:60] for f in range(8)],
                     rng)
    return (jnp.asarray(x[tr]), jnp.asarray(y[tr]),
            jnp.asarray(x[te_idx]), jnp.asarray(y[te_idx]))


# ---------------------------------------------------------------------------
# model / train / eval
# ---------------------------------------------------------------------------


def _model_fns(cfg: ExperimentConfig):
    if cfg.task == "image":
        mc = CNNConfig(image_size=cfg.image_size, conv_features=(8, 16),
                       hidden=64, n_classes=cfg.n_super)
        init = lambda k: init_cnn(k, mc)
        fwd = cnn_forward
    else:
        mc = LSTMCNNConfig(conv_features=(16, 32), lstm_hidden=32, n_classes=4)
        init = lambda k: init_lstm_cnn(k, mc)
        fwd = lstm_cnn_forward

    def train_fn(params, batch, key):
        xb, yb = batch
        g = jax.grad(lambda p: xent_loss(fwd(p, xb), yb))(params)
        return jax.tree.map(lambda p, gg: p - cfg.lr * gg, params, g)

    def eval_fn(params, xd, yd):
        return accuracy(fwd(params, xd), yd)

    return init, train_fn, eval_fn


def _sample_batches(key, X, Y, batch):
    """X: [P, N, ...] -> random [P, B, ...] minibatches."""
    p, n = X.shape[0], X.shape[1]
    idx = jax.random.randint(key, (p, batch), 0, n)
    xb = jnp.take_along_axis(X, idx.reshape((p, batch) + (1,) * (X.ndim - 2)),
                             axis=1)
    yb = jnp.take_along_axis(Y, idx, axis=1)
    return xb, yb


def _make_pretrain(train_fn, cfg: "ExperimentConfig", n_clients: int,
                   Xtr=None, Ytr=None):
    """Per-device local pretraining as one ``lax.scan`` over pretrain_steps.

    Preserves the former Python loop's ``split(key, 3)`` chain bitwise.
    With ``Xtr/Ytr`` bound the result is ``(models, key) -> models``;
    without, it is ``(models, key, Xtr, Ytr) -> models`` — the
    data-as-argument form ``run_sweep_experiment`` vmaps over seeds.
    """
    def pretrain(models, key, X, Y):
        def body(carry, _):
            models, key = carry
            key, kb, kt = jax.random.split(key, 3)
            batches = _sample_batches(kb, X, Y, cfg.batch)
            keys = jax.random.split(kt, n_clients)
            models = jax.vmap(train_fn)(models, batches, keys)
            return (models, key), None

        (models, _), _ = jax.lax.scan(body, (models, key), None,
                                      length=cfg.pretrain_steps)
        return models

    if Xtr is None:
        return pretrain
    return lambda models, key: pretrain(models, key, Xtr, Ytr)


# ---------------------------------------------------------------------------
# mobility stream
# ---------------------------------------------------------------------------


def _mobility_tensors(cfg: ExperimentConfig):
    """Precomputed co-location schedule (see repro.scenarios.registry).

    Returns (colocation dict with fixed_id/exchange [T, M], pos [T, M, 2],
    area [M]; plus init_space/init_area), mule_space [M], mule_area [M].
    """
    if cfg.scenario:
        co = get_scenario(cfg.scenario).colocation(cfg.seed, cfg.n_mules,
                                                   cfg.steps)
    elif cfg.pattern == "4q":
        visits = synth_foursquare_trace(cfg.seed, n_users=cfg.n_mules,
                                        n_places=8, n_steps=cfg.steps)
        co = trace_colocation(visits, cfg.n_mules, cfg.steps)
    else:
        co = walk_colocation(cfg.seed, cfg.n_mules, cfg.steps,
                             p_cross=float(cfg.pattern))
    return co, co["init_space"], co["init_area"]


# ---------------------------------------------------------------------------
# main experiment driver
# ---------------------------------------------------------------------------


def _mule_mesh(n_mules: int):
    """(1, k) pod x data mesh over the largest divisor of n_mules that the
    device pool covers — the forced-host-device lane of ``--distributed``.

    Prints the mesh it settled on: the population must divide the data
    axis, so a prime ``n_mules`` (or a single-accelerator host, where the
    host-device forcing doesn't apply) degrades to k=1 — still the
    distributed code path, but with nothing actually sharded.
    """
    n_dev = jax.device_count()
    if jax.process_count() > 1:
        # a multi-process mesh must span every process's devices (a rank
        # with no mesh slot would never join the collectives), so the
        # divisor search can't shrink the pool — the population has to fit
        if n_mules % n_dev:
            raise ValueError(
                f"multi-process run: n_mules={n_mules} must divide over "
                f"all {n_dev} devices ({jax.process_count()} processes x "
                f"{jax.local_device_count()} local)")
        k = n_dev
    else:
        k = max(s for s in range(1, min(n_dev, n_mules) + 1)
                if n_mules % s == 0)
    print(f"distributed mesh: 1 pod x {k} mule shards "
          f"({n_dev} devices visible, n_mules={n_mules})"
          + (" — WARNING: k=1 shards nothing" if k == 1 else ""))
    from repro.launch.mesh import make_mule_mesh
    return make_mule_mesh(1, k)


def run_experiment(cfg: ExperimentConfig) -> Dict:
    t_start = time.time()
    if cfg.scenario:
        spec = get_scenario(cfg.scenario)
        cfg = dataclasses.replace(cfg, mode=spec.mode, dist=spec.dist,
                                  task=spec.task, n_fixed=spec.n_fixed)
    init, train_fn, eval_fn = _model_fns(cfg)
    colocation, mule_space, mule_area = _mobility_tensors(cfg)

    if cfg.mode == "fixed":
        Xtr, Ytr, Xte, Yte = _image_data_fixed(cfg)
        n_clients = cfg.n_fixed
    else:
        if cfg.task == "image":
            Xtr, Ytr, Xte, Yte = _image_data_mobile(cfg, mule_space, mule_area)
        else:
            Xtr, Ytr, Xte, Yte = _har_data_mobile(cfg, mule_space, mule_area)
        n_clients = cfg.n_mules

    key = jax.random.PRNGKey(cfg.seed + 100)
    eval_v = jax.jit(jax.vmap(eval_fn))

    # -- per-device local pretraining (paper Sec 4.2.1 / 4.3.1) --------------
    # one compiled lax.scan over pretrain_steps (was: one jitted dispatch per
    # step x pretrain_steps), preserving the split(key, 3) chain bitwise
    pretrain = _make_pretrain(train_fn, cfg, n_clients, Xtr, Ytr)
    pre_models = jax.jit(pretrain)(jax.vmap(init)(
        jax.random.split(jax.random.PRNGKey(cfg.seed), n_clients)),
        jax.random.PRNGKey(cfg.seed + 7))

    def eval_fixed_models(models):
        """Evaluate stacked fixed-device models on their space test sets."""
        return np.asarray(eval_v(models, Xte, Yte))

    def eval_mobile_models(models, cur_fid):
        """Each mule evaluated on the test set of its current/last space."""
        fid = np.asarray(cur_fid).clip(0)
        Xm = Xte[fid]
        Ym = Yte[fid]
        return np.asarray(eval_v(models, Xm, Ym))

    traces = []
    sizes = jnp.full((n_clients,), float(Xtr.shape[1]))

    # ---------------- federated baselines (round-based, no mobility) --------
    if cfg.method in ("fedavg", "cfl", "fedas"):
        from repro.core.aggregation import weighted_average
        n_rounds = cfg.steps // 10
        model = weighted_average(pre_models, sizes)
        if cfg.method == "cfl":
            st = CFLState(clusters=[np.arange(n_clients)], models=[model],
                          eps1=0.5, eps2=0.05)
        if cfg.method == "fedas":
            clients = pre_models
        for r in range(n_rounds):
            key, kb, kr = jax.random.split(key, 3)
            batches = _sample_batches(kb, Xtr, Ytr, cfg.batch)
            if cfg.method == "fedavg":
                model = fedavg_round(model, batches, sizes, train_fn, kr,
                                     local_steps=2)
                stacked = jax.tree.map(
                    lambda l: jnp.broadcast_to(l[None], (n_clients,) + l.shape),
                    model)
            elif cfg.method == "cfl":
                st = cfl_round(st, batches, sizes, train_fn, kr, local_steps=2)
                stacked = cfl_client_models(st, n_clients)
            else:
                model, clients = fedas_round(model, clients, batches, sizes,
                                             train_fn, kr)
                stacked = clients
            if (r + 1) % max(cfg.eval_every // 10, 1) == 0:
                acc = eval_fixed_models(stacked) if cfg.mode == "fixed" else \
                    eval_mobile_models(stacked, np.arange(n_clients) % cfg.n_fixed)
                # log the post-step index (round r covers steps
                # [r*10, (r+1)*10)), matching the mobility methods' x-axis
                traces.append(((r + 1) * 10 - 1, float(acc.mean())))
        final_models = stacked

    # ---------------- mobility-coupled methods (all on the scan engine) ------
    else:
        fresh = (FreshnessConfig(init_threshold=1e9, warmup=10**9)
                 if cfg.freshness_off else FreshnessConfig())
        pcfg = PopulationConfig(
            mode=cfg.mode, n_fixed=cfg.n_fixed, n_mules=cfg.n_mules,
            gamma=cfg.gamma, freshness=fresh)
        pop = init_population(jax.random.PRNGKey(cfg.seed), init, pcfg)
        if cfg.mode == "fixed":
            # fixed devices hold the pretrained models; each mule starts with
            # a snapshot from its initial space (its user's 'home' space)
            pop["fixed_models"] = pre_models
            home = jnp.asarray(mule_area * 4 + mule_space, jnp.int32)
            pop["mule_models"] = jax.tree.map(lambda l: l[home], pre_models)
        else:
            pop["mule_models"] = pre_models

        def batch_fn(kb, t):
            sampled = _sample_batches(kb, Xtr, Ytr, cfg.batch)
            if cfg.mode == "fixed":
                return {"fixed": sampled, "mule": None}
            return {"fixed": None, "mule": sampled}

        if cfg.mode == "fixed":
            eval_hook = lambda st, last: eval_v(st["fixed_models"], Xte, Yte)
        else:
            eval_hook = lambda st, last: eval_v(st["mule_models"],
                                                Xte[last], Yte[last])

        # all mobility methods draw per-step keys as fold_in(ke, t) — the
        # engine's documented discipline — so at a fixed seed every method
        # trains on identical batch draws and curves differ only by method;
        # the whole schedule (method dispatch, t%3 cadences, in-scan eval)
        # is one compiled program. The input population is not read again,
        # so its buffers are donated and the replay updates in place.
        key, ke = jax.random.split(key)
        generator = None
        if cfg.stream:
            # streamed replay: the schedule is generated chunk-by-chunk
            # inside the compiled scan. Scenarios with a native generator
            # stream procedurally; everything else streams the compacted
            # form of the colocation already built for the data partition.
            generator = (scenario_generator(cfg.scenario, cfg.seed,
                                            cfg.n_mules, cfg.steps,
                                            colocation=colocation)
                         if cfg.scenario else compact_colocation(colocation))
        if cfg.distributed:
            # mule-sharded replay: every method lowers through the one
            # MethodProgram table (the peer baselines ring their encounter
            # search around the mesh). In mobile mode the in-scan eval hook
            # would read sharded mule models shard-locally, so evaluation
            # happens once on the gathered final state instead.
            from repro.core.distributed import (DistributedConfig,
                                                to_distributed_state)
            from repro.scenarios import run_population_distributed
            dcfg = DistributedConfig(pop=pcfg,
                                     rebucket_every=cfg.rebucket_every,
                                     rebucket_threshold=cfg.rebucket_threshold)
            mesh = _mule_mesh(cfg.n_mules)
            dist_eval = cfg.mode == "fixed"
            if cfg.stream:
                chunk = cfg.stream_chunk or (
                    cfg.rebucket_every or
                    (cfg.eval_every if dist_eval else 64))
                pop, aux = run_population_streamed(
                    to_distributed_state(pop, dcfg), generator, batch_fn,
                    train_fn, pcfg, ke, n_steps=cfg.steps, chunk_len=chunk,
                    eval_every=cfg.eval_every if dist_eval else None,
                    eval_fn=eval_hook if dist_eval else None,
                    method=cfg.method, donate=True, mesh=mesh, dcfg=dcfg)
            else:
                pop, aux = run_population_distributed(
                    to_distributed_state(pop, dcfg), colocation, batch_fn,
                    train_fn, dcfg, mesh, ke,
                    eval_every=cfg.eval_every if dist_eval else None,
                    eval_fn=eval_hook if dist_eval else None,
                    method=cfg.method, donate=True)
            if jax.process_count() > 1:
                # multi-process cluster: the metrics below np-read and
                # fancy-index the final state, which multi-host arrays
                # refuse — pull every leaf back to host numpy (sharded
                # leaves allgather their row blocks, replicated leaves
                # read the local replica)
                from repro.launch.multiprocess import gather_global
                pop = jax.tree.map(gather_global, pop)
                aux = jax.tree.map(gather_global, aux)
        elif cfg.stream:
            pop, aux = run_population_streamed(
                pop, generator, batch_fn, train_fn, pcfg, ke,
                n_steps=cfg.steps,
                chunk_len=cfg.stream_chunk or cfg.eval_every,
                eval_every=cfg.eval_every, eval_fn=eval_hook,
                method=cfg.method, donate=True)
        else:
            pop, aux = run_population(pop, colocation, batch_fn, train_fn,
                                      pcfg, ke, eval_every=cfg.eval_every,
                                      eval_fn=eval_hook, method=cfg.method,
                                      donate=True)
        traces = ([] if aux["evals"] is None else
                  [(int(s), float(np.mean(a))) for s, a in
                   zip(aux["eval_steps"], np.asarray(aux["evals"]))])
        last_fid = aux["last_fid"]
        final_models = (pop["fixed_models"] if cfg.mode == "fixed"
                        else pop["mule_models"])

    # ---------------- final metrics (pre/post local) --------------------------
    if cfg.mode == "fixed":
        pre = eval_fixed_models(final_models)
        post_models = final_models
        for _ in range(cfg.post_local_epochs):
            key, kb, kt = jax.random.split(key, 3)
            batches = _sample_batches(kb, Xtr, Ytr, cfg.batch)
            keys = jax.random.split(kt, n_clients)
            post_models = jax.vmap(train_fn)(post_models, batches, keys)
        post = eval_fixed_models(post_models)
    else:
        pre = eval_mobile_models(final_models, last_fid if cfg.method not in
                                 ("fedavg", "cfl", "fedas") else
                                 np.arange(n_clients) % cfg.n_fixed)
        post = pre

    return {
        "config": dataclasses.asdict(cfg),
        "trace": traces,
        "pre_local_acc": float(np.mean(pre)),
        "post_local_acc": float(np.mean(post)),
        "wall_s": time.time() - t_start,
    }


# ---------------------------------------------------------------------------
# batched multi-seed sweeps
# ---------------------------------------------------------------------------


def _stack_wrap_pad(arrs: List[np.ndarray]) -> jnp.ndarray:
    """Stack per-seed [P, N, ...] arrays whose N varies across seeds.

    Shorter pools are padded to the longest with uniformly-drawn repeats
    (fixed rng, mirroring ``_pad_to``), so no sample is *systematically*
    over-weighted; any individual repeat still tilts that seed's empirical
    sampling/eval weights slightly, which is why per-seed sweep metrics
    can differ from an unpadded ``run_experiment`` at the same seed.
    """
    rng = np.random.default_rng(0)
    n = max(a.shape[1] for a in arrs)
    out = []
    for a in arrs:
        a = np.asarray(a)
        idx = np.concatenate([np.arange(a.shape[1]),
                              rng.integers(0, a.shape[1], n - a.shape[1])])
        out.append(a[:, idx])
    return jnp.asarray(np.stack(out))


def run_sweep_experiment(cfg: ExperimentConfig, seeds: Sequence[int],
                         methods: Optional[Sequence[str]] = None) -> Dict:
    """Seed-averaged multi-method sweep on the batched scan engine.

    Builds per-seed datasets, mobility schedules, and pretrained
    populations, stacks them on a leading seed axis, and replays every
    requested method with ``run_sweep`` — one vmapped compiled program per
    method instead of ``len(seeds) x len(methods)`` retraced runs. The
    federated baselines (fedavg/cfl/fedas) are round-based and not on the
    engine; request those through ``run_experiment``.

    Returns per-method seed-stacked and seed-averaged accuracy curves on
    the shared post-step x-axis (``eval_steps``).
    """
    t_start = time.time()
    methods = list(methods or [cfg.method])
    bad = [m for m in methods if m not in METHODS_MOBILE]
    if bad:
        raise ValueError(f"not engine methods: {bad}; pick from "
                         f"{METHODS_MOBILE}")
    if cfg.scenario:
        spec = get_scenario(cfg.scenario)
        cfg = dataclasses.replace(cfg, mode=spec.mode, dist=spec.dist,
                                  task=spec.task, n_fixed=spec.n_fixed)
    init, train_fn, eval_fn = _model_fns(cfg)
    n_clients = cfg.n_fixed if cfg.mode == "fixed" else cfg.n_mules

    # -- per-seed assembly (numpy-level), stacked on a leading [S] axis ------
    cos, homes, inits, pre_keys, run_keys = [], [], [], [], []
    Xtr_l, Ytr_l, Xte_l, Yte_l = [], [], [], []
    for s in seeds:
        scfg = dataclasses.replace(cfg, seed=int(s))
        co, mule_space, mule_area = _mobility_tensors(scfg)
        if cfg.mode == "fixed":
            Xtr, Ytr, Xte, Yte = _image_data_fixed(scfg)
        elif cfg.task == "image":
            Xtr, Ytr, Xte, Yte = _image_data_mobile(scfg, mule_space,
                                                    mule_area)
        else:
            Xtr, Ytr, Xte, Yte = _har_data_mobile(scfg, mule_space, mule_area)
        cos.append(co)
        homes.append(jnp.asarray(mule_area * 4 + mule_space, jnp.int32))
        inits.append(jax.vmap(init)(
            jax.random.split(jax.random.PRNGKey(int(s)), n_clients)))
        pre_keys.append(jax.random.PRNGKey(int(s) + 7))
        # same chain run_experiment uses: ke = split(PRNGKey(seed + 100))[1]
        run_keys.append(jax.random.split(
            jax.random.PRNGKey(int(s) + 100))[1])
        Xtr_l.append(Xtr)
        Ytr_l.append(Ytr)
        Xte_l.append(Xte)
        Yte_l.append(Yte)
    context = (_stack_wrap_pad(Xtr_l), _stack_wrap_pad(Ytr_l),
               _stack_wrap_pad(Xte_l), _stack_wrap_pad(Yte_l))

    # -- vmapped pretraining: one compiled scan for all seeds ----------------
    pretrain = _make_pretrain(train_fn, cfg, n_clients)
    pre_models = jax.jit(jax.vmap(pretrain))(
        stack_trees(inits), stack_trees(pre_keys), context[0], context[1])

    fresh = (FreshnessConfig(init_threshold=1e9, warmup=10**9)
             if cfg.freshness_off else FreshnessConfig())
    pcfg = PopulationConfig(mode=cfg.mode, n_fixed=cfg.n_fixed,
                            n_mules=cfg.n_mules, gamma=cfg.gamma,
                            freshness=fresh)
    pops = stack_trees([init_population(jax.random.PRNGKey(int(s)), init,
                                        pcfg) for s in seeds])
    if cfg.mode == "fixed":
        pops["fixed_models"] = pre_models
        pops["mule_models"] = jax.vmap(
            lambda pre, home: jax.tree.map(lambda l: l[home], pre))(
                pre_models, stack_trees(homes))
    else:
        pops["mule_models"] = pre_models

    def batch_fn(kb, t, ctx):
        sampled = _sample_batches(kb, ctx[0], ctx[1], cfg.batch)
        if cfg.mode == "fixed":
            return {"fixed": sampled, "mule": None}
        return {"fixed": None, "mule": sampled}

    if cfg.mode == "fixed":
        eval_hook = lambda st, last, ctx: jax.vmap(eval_fn)(
            st["fixed_models"], ctx[2], ctx[3])
    else:
        eval_hook = lambda st, last, ctx: jax.vmap(eval_fn)(
            st["mule_models"], ctx[2][last], ctx[3][last])

    out = run_sweep(pops, stack_colocations(cos), batch_fn, train_fn, pcfg,
                    stack_trees(run_keys), eval_every=cfg.eval_every,
                    eval_fn=eval_hook, methods=tuple(methods),
                    context=context)

    final_eval = jax.jit(jax.vmap(eval_hook))
    result_methods, eval_steps = {}, np.zeros((0,), int)
    for m, (final, aux) in out.items():
        eval_steps = aux["eval_steps"]
        acc = (np.asarray(aux["evals"]).mean(axis=-1)
               if aux["evals"] is not None
               else np.zeros((len(list(seeds)), 0)))     # [S, E]
        facc = np.asarray(final_eval(final, aux["last_fid"],
                                     context)).mean(axis=-1)  # [S]
        result_methods[m] = {
            "acc": acc.tolist(),
            "mean_acc": acc.mean(axis=0).tolist(),
            "final_acc": facc.tolist(),
            "mean_final_acc": float(facc.mean()),
        }

    return {
        "config": dataclasses.asdict(cfg),
        "seeds": [int(s) for s in seeds],
        "eval_steps": [int(x) for x in eval_steps],
        "methods": result_methods,
        "wall_s": time.time() - t_start,
    }
