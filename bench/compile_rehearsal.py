#!/usr/bin/env python3
"""Compile a sim cell's first segment program for a described TPU v5e,
without the chip, and print what one chip would hold.

    JAX_PLATFORMS=cpu python3 bench/compile_rehearsal.py --config bench/configs/afa_mnist_k400.json

Compiles the segment the cell's first experiment runs, once on one described
chip (``client_shards`` forced to 0) and once client-sharded over the
configuration's ``client_shards`` chips of a described ``v5e:2x2``, and
prints each program's per-chip bytes from ``memory_analysis`` (arguments +
outputs + temporaries), or the compiler's refusal.  Kernel wrappers take
their TPU geometry (``repro.kernels.ops._on_tpu`` is steered here).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _structs(tree, sharding_of):
    import jax

    return jax.tree_util.tree_map(
        lambda v, s: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s),
        tree, sharding_of(tree))


def compile_segment(cfg: dict, topo, shards: int) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from repro.data import SyntheticClassification
    from repro.fed import engine, simulator
    from repro.fed.server import ServerConfig
    from repro.fed.simulator import SimConfig
    from repro.kernels.policy import KernelPlan
    from repro.launch import mesh as mesh_mod

    K, sizes = cfg["clients"], tuple(cfg["model"]["sizes"])
    data = SyntheticClassification(
        np.zeros((cfg["n_train"], sizes[0]), np.float32),
        np.zeros((cfg["n_train"],), np.int32),
        np.zeros((cfg["n_test"], sizes[0]), np.float32),
        np.zeros((cfg["n_test"],), np.int32), cfg["classes"])
    sim = SimConfig(num_clients=K, bad_frac=cfg["bad_frac"], scenario="byzantine",
                    rounds=24, local_epochs=cfg["local_epochs"],
                    batch_size=cfg["batch_size"], hidden=sizes[1:-1], engine="fused",
                    segment_rounds=4, client_shards=shards)
    variant = cfg["afa_variant"] if shards == 0 else "iterative"
    server = ServerConfig(rule="afa", num_clients=K, afa_variant=variant,
                          kernel_plan=KernelPlan(mode="pallas"))
    if shards:
        mesh = jax.sharding.Mesh(np.array(topo.devices[:shards]), ("client",))
        mesh_mod.make_client_mesh = lambda n: mesh
        axis = "client"
        data_in, state_out, _ = engine._client_shard_specs(axis)
        specs = lambda params: (jax.tree_util.tree_map(lambda _: P(), params),
                                state_out, data_in, P(axis), P(axis))

        def place(m, params, state, d, bad, ids):
            shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                           specs(params))
            return tuple(_structs(v, lambda t, s=s: s)
                         for v, s in zip((params, state, d, bad, ids), shard))

        simulator.place_on_client_mesh = place
        scalar = NamedSharding(mesh, P())
    else:
        scalar = SingleDeviceSharding(topo.devices[0])
    seg_fn, args = simulator.first_segment(data, sim, server)
    if not shards:
        args = _structs(args, lambda t: jax.tree_util.tree_map(lambda _: scalar, t))
    else:
        args = args[:2] + (jax.ShapeDtypeStruct((), np.uint32, sharding=scalar),) \
            + args[3:6] + (jax.ShapeDtypeStruct((), np.int32, sharding=scalar),)
    t0 = time.perf_counter()
    try:
        compiled = seg_fn.lower(*args).compile()
    except Exception as e:  # the compiler's refusal is the reading
        return dict(clients=K, chips=max(shards, 1), refused=str(e)[:600],
                    compile_s=time.perf_counter() - t0)
    mem = compiled.memory_analysis()
    return dict(
        clients=K, chips=max(shards, 1), compile_s=time.perf_counter() - t0,
        argument_bytes=int(mem.argument_size_in_bytes),
        output_bytes=int(mem.output_size_in_bytes),
        temp_bytes=int(mem.temp_size_in_bytes),
        program_bytes=int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                          + mem.temp_size_in_bytes),
        kernels="tpu_custom_call" in compiled.as_text(),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--clients", type=int, help="override the configuration's K")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = lambda: True
    cfg = json.loads(Path(args.config).read_text())
    if args.clients:
        cfg = dict(cfg, clients=args.clients,
                   n_train=args.clients * cfg["samples_per_client"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for shards in (cfg["client_shards"], 0):
        print(json.dumps(compile_segment(cfg, topo, shards)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
