"""A cell's chunk program compiled for v5e:2x2 from a machine with no chip:
sizes and the compile's seconds, never times. Run from the sandbox:

    JAX_PLATFORMS=cpu python3 scripts/chunk_program_census.py ouro_q.preset

It prints the program's generated-code size, temporaries and arguments
(``memory_analysis()``), how long lowering and compiling took HERE (a count
of the compiler's work, not of the chip's), the Mosaic calls by kernel, and
every instruction of stage ``act`` that writes an array at least as large as
one acting ring (a ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` of
``[lanes, history, KV, D]`` float32): the acting step is to write none — a
turn's key goes into its slot in place and ``decode`` reads the ring where
it lies. ``CENSUS_HLO=<file>`` keeps the compiled program's text.
"""
import argparse
import json
import math
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
CHECKOUT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHECKOUT))

_SHAPE = re.compile(r"= \(?(f32|bf16)\[([\d,]+)\]")
_KERNEL = re.compile(r'custom_call_target="tpu_custom_call".*?'
                     r'op_name="[^"]*/(\w+)/pallas_call"')


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("--set", nargs="*", default=[], metavar="PATH=VALUE")
    args = p.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.config import apply_overrides
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.r2d2_loop import make_r2d2_train
    from dist_dqn_tpu.telemetry import stages
    from perf.harness.manifest import Manifest, resolve_cell
    from perf.harness.run_cell import build_config

    jax.config.update("jax_enable_compilation_cache", False)
    loop_common.pallas_routing = lambda enabled: (enabled, False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    plan = resolve_cell(Manifest(CHECKOUT), args.cell)
    cfg = apply_overrides(build_config(plan), args.set)
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_r2d2_train(cfg, env, net)
    one = SingleDeviceSharding(topo.devices[0])
    carry = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(init, jax.ShapeDtypeStruct((2,), np.uint32)))
    t0 = time.perf_counter()
    lowered = jax.jit(run_chunk, static_argnums=1, donate_argnums=0).lower(
        carry, int(plan["chunk_iters"]))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    ring = max((math.prod(leaf.shape) * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(jax.eval_shape(
                    lambda: net.initial_state(cfg.actor.num_envs)))),
               default=0)
    stage = stages.table_from_text(text)
    large = []
    for _, match, line in stages.instructions(text):
        shape = _SHAPE.search(line)
        if (ring and shape and stage.get(match.group("inst")) == "act"
                and match.group("op") in ("copy", "dynamic-slice",
                                          "dynamic-update-slice", "fusion")):
            nbytes = (4 if shape.group(1) == "f32" else 2) * math.prod(
                int(d) for d in shape.group(2).split(","))
            # a fusion that writes a slot into the ring it was handed
            # (``aliasing_operands``) returns that ring's own buffer
            if nbytes >= ring and not (match.group("op") == "fusion"
                                       and "aliasing_operands" in line):
                large.append((match.group("inst"), match.group("op"),
                              nbytes))
    kernels = {}
    for name in _KERNEL.findall(text):
        kernels[name] = kernels.get(name, 0) + 1
    print(json.dumps({
        "cell": args.cell, "overrides": args.set,
        "generated_code_mb": m.generated_code_size_in_bytes / 1e6,
        "temp_gb": m.temp_size_in_bytes / 1e9,
        "argument_gb": m.argument_size_in_bytes / 1e9,
        "lower_s_here": t1 - t0, "compile_s_here": t2 - t1,
        "instructions": sum(1 for _ in stages.instructions(text)),
        "mosaic_calls": kernels, "ring_bytes": ring,
        "act_ring_sized_writes": large,
    }), flush=True)
    out = os.environ.get("CENSUS_HLO")
    if out:
        Path(out).write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
