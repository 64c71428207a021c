"""Chip-targeted compile rehearsal: every cell's chunk program at its real
sizes, compiled for v5e:2x2 from a machine with NO chip (libtpu's
ahead-of-time path). Sizes, never times. Run from the sandbox:

    JAX_PLATFORMS=cpu python3 perf/tools/compile_rehearsal.py [cell ...]

It catches an out-of-memory, a Mosaic refusal or a sharding error before any
chip time is spent, and prints each program's ``memory_analysis()``.
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))


def main(cells) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.parallel import (make_mesh, make_mesh_fused_train,
                                       make_mesh_r2d2_train)
    from dist_dqn_tpu.r2d2_loop import make_r2d2_train
    from dist_dqn_tpu.train_loop import make_fused_train
    from perf.harness.manifest import Manifest, resolve_cell
    from perf.harness.run_cell import build_config

    jax.config.update("jax_enable_compilation_cache", False)
    # The routing asks jax.default_backend(), which is the CPU here: steer
    # it in this script (not through an option of the program).
    loop_common.pallas_routing = lambda enabled: (enabled, False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    manifest = Manifest(CHECKOUT)
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    for name in cells or [w["name"] for w in manifest.data["workloads"]]:
        plan = resolve_cell(manifest, name)
        cfg = build_config(plan)
        env = make_jax_env(cfg.env_name)
        net = build_network(cfg.network, env.num_actions)
        # train.train's own routing: a recurrent network takes the
        # sequence loop.
        one_chip, mesh_train = (
            (make_r2d2_train, make_mesh_r2d2_train) if cfg.network.lstm_size
            else (make_fused_train, make_mesh_fused_train))
        if plan["num_devices"] == 1:
            init, run_chunk = one_chip(cfg, env, net)
            one = SingleDeviceSharding(topo.devices[0])
            carry = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
                jax.eval_shape(init, key))
            run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
        else:
            mesh = make_mesh(devices=topo.devices[:plan["num_devices"]])
            init, run = mesh_train(cfg, env, net, mesh)
            carry = init.lower(key).compile().output_shardings
            shapes = jax.eval_shape(init, key)
            carry = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                shapes, carry)
        compiled = run.lower(carry, int(plan["chunk_iters"])).compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "cell": name, "chunk_iters": plan["chunk_iters"],
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "mosaic_kernel": "per_stratified_sample" in text,
            "all_reduces": text.count(" all-reduce("),
            "all_reduce_starts": text.count(" all-reduce-start("),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
