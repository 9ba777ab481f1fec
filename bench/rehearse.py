"""Compile a cell's chunk program for a described TPU v5e, without a chip.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [--mules M ...]

Builds the engine's streamed chunk program exactly as a run does (the same
``get_compiled_chunk_replay`` entry, weights, dataset, generator and SGD
step, as shapes only), compiles it with the TPU compiler for one chip of a
``v5e:2x2`` topology (for a cell on a mule mesh, for the mesh over its
chips, giving the bytes on each), and
prints one JSON line per population size: the bytes ``memory_analysis``
gives (arguments, outputs, temporaries, aliased) against the chip's 16 GB,
and of the arguments, the population state's (``state_bytes``: models,
timestamps, freshness) and the frozen base's (``shared_bytes``, for a
configuration with ``init_shared``: one copy among the arguments, whatever
the mules). A size that does not fit fails to compile and says so.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def compile_chunk(cell, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    import program as prog_mod
    from repro.scenarios.engine import get_compiled_chunk_replay

    ref = cell.reference
    inputs = prog_mod.make_inputs(cell, ref, 0, jax.devices("cpu")[0])
    mesh_cfg = cell.traffic.get("mesh")
    cell.traffic = dict(cell.traffic, mesh=None)
    prog = prog_mod.make_program(cell, inputs)
    state = jax.eval_shape(lambda: prog.initial_state(
        *prog_mod.weights(cell, ref, inputs)))
    if mesh_cfg:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.distributed import (DistributedConfig,
                                            to_distributed_state)
        n = mesh_cfg["pod"] * mesh_cfg["data"]
        prog.mesh = Mesh(np.array(topo.devices[:n]).reshape(
            mesh_cfg["pod"], mesh_cfg["data"]), ("pod", "data"))
        prog.dcfg = DistributedConfig(pop=prog.pcfg)
        state = jax.eval_shape(lambda s: to_distributed_state(s, prog.dcfg),
                               state)
        whole = NamedSharding(prog.mesh, P())
        split = NamedSharding(prog.mesh, P(prog.dcfg.data_axis))
        specs = prog.generator.specs(prog.dcfg.data_axis)
    else:
        whole = split = SingleDeviceSharding(topo.devices[0])
        specs = None

    def place(tree, sharding=whole):
        return jax.tree.map(lambda l: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=sharding), tree)

    state = {k: place(v, split if k.startswith("mule") else whole)
             for k, v in state.items()}
    gen_arrays = prog.generator.arrays()
    gen_arrays = (place(gen_arrays) if specs is None else
                  jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
                      l.shape, l.dtype, sharding=NamedSharding(prog.mesh, s)),
                      gen_arrays, specs))
    last = place(jax.ShapeDtypeStruct((cell.traffic["mules"],), jnp.int32),
                 split)
    t0 = place(jax.ShapeDtypeStruct((), jnp.int32))
    ctx = place(jax.eval_shape(lambda: inputs.context))
    key = place(jax.eval_shape(lambda: inputs.key))
    train_fn, batch_fn = prog.engine_fns
    fn = get_compiled_chunk_replay(
        state, prog.generator, gen_arrays, batch_fn, ctx, key,
        train_fn, prog.pcfg, method=prog.method, eval_every=None,
        eval_fn=None, chunk_len=prog.chunk_len, donate=True, mesh=prog.mesh,
        dcfg=prog.dcfg)
    compiled = fn.lower(state, last, t0, gen_arrays, None, ctx,
                        key).compile()
    return compiled, {"state_bytes": _bytes(state),
                      "shared_bytes": _bytes(ctx.get("shared"))}


def _bytes(tree) -> int:
    """Bytes of a tree's leaves, whole (not per chip)."""
    import jax
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mules", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from spec import load_cell
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = load_cell(args.workload)
    for m in args.mules or [cell.traffic["mules"]]:
        cell.traffic = dict(cell.traffic, mules=m)
        row = {"workload": cell.name, "mules": m}
        try:
            compiled, parts = compile_chunk(cell, topo)
            ma = compiled.memory_analysis()
            row.update(parts)
            row.update({k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")})
            row["total_gib"] = (row["argument_size_in_bytes"]
                                + row["output_size_in_bytes"]
                                + row["temp_size_in_bytes"]
                                - row["alias_size_in_bytes"]) / 2 ** 30
        except Exception as e:  # a size that does not fit is a finding
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
