"""The guard "this cell's chunk program did not move": lower the chunk
program of each named cell for v5e (the kernels routed as on a TPU) and for
the CPU (the plain paths) WITHOUT a chip, and print a hash of the StableHLO
text without source locations, beside a hash of the parameter tree's paths,
shapes and dtypes. Run it in two checkouts and compare the lines:

    JAX_PLATFORMS=cpu python3 scripts/chunk_program_hash.py r2d2.preset ...

A Mosaic kernel's body rides in its custom call as MLIR bytecode WITH the
locations of the Python lines that traced it (and the checkout's path), so
the body is parsed and printed without them before it is hashed: two
checkouts that differ only in where their lines lie hash alike.
"""
import base64
import hashlib
import json
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
CHECKOUT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHECKOUT))
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def without_kernel_locations(text: str) -> str:
    """``text`` with every Mosaic body replaced by the hash of its module
    printed without debug information."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True

    def hashed(match):
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            plain = module.operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22' + hashlib.sha256(
            plain.encode()).hexdigest() + '\\22'

    return _BODY.sub(hashed, text)


def main(cells) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.r2d2_loop import make_r2d2_train
    from dist_dqn_tpu.train_loop import make_fused_train
    from perf.harness.manifest import Manifest, resolve_cell
    from perf.harness.run_cell import build_config

    jax.config.update("jax_enable_compilation_cache", False)
    as_it_is = loop_common.pallas_routing
    chip = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    manifest = Manifest(CHECKOUT)
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    for name in cells:
        plan = resolve_cell(manifest, name)
        if plan["num_devices"] != 1:
            raise SystemExit(f"{name}: a one-chip cell's program only")
        cfg = build_config(plan)
        env = make_jax_env(cfg.env_name)
        net = build_network(cfg.network, env.num_actions)
        build = (make_r2d2_train if hasattr(net, "initial_state")
                 else make_fused_train)
        out = {"cell": name}
        for target in ("tpu", "cpu"):
            # the routing asks jax.default_backend(), the CPU here: steered
            # in this script, as perf/tools/compile_rehearsal.py does
            loop_common.pallas_routing = (
                (lambda enabled: (enabled, False)) if target == "tpu"
                else as_it_is)
            init, run_chunk = build(cfg, env, net)
            carry = jax.eval_shape(init, key)
            if target == "tpu":
                one = SingleDeviceSharding(chip)
                carry = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=one), carry)
            text = jax.jit(run_chunk, static_argnums=1, donate_argnums=0
                           ).lower(carry, int(plan["chunk_iters"])).as_text()
            out[target] = hashlib.sha256(without_kernel_locations(
                text).encode()).hexdigest()[:16]
        leaves = jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(init, key))[0]
        out["carry_tree"] = hashlib.sha256(json.dumps(
            [(jax.tree_util.keystr(path), leaf.shape, str(leaf.dtype))
             for path, leaf in leaves]).encode()).hexdigest()[:16]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
