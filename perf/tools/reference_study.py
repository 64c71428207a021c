"""How far the program's learner step is from the plain reference, over many
seeds: the distribution the tolerances in ``harness/reference_check.py`` are
set from. One process, on the chip, at the configurations' own widths:

    python3 perf/tools/reference_study.py --seeds 64 \
        --out chiprun_out/reference_study.json

Every distinct (configuration, learner, network, rows per shard) among the
cells of ``BENCHMARK.json`` is checked once per seed — exactly the check a
run of such a cell makes with that ``--seed``.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from statistics import median

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, default=64)
    p.add_argument("--seed-base", type=int, default=0)
    p.add_argument("--out", type=Path, required=True)
    p.add_argument("--cells", nargs="*", default=None,
                   help="only the checks of these cells (default: all)")
    p.add_argument("--control", type=int, default=0, metavar="N",
                   help="also draw N seeds of the control: the program's "
                        "network on float8-rounded weights "
                        "(reference_check.CoarseNet), which has to fail")
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args()

    import jax

    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.utils import backend
    from perf.harness import reference_check
    from perf.harness.manifest import Manifest, resolve_cell
    from perf.harness.run_cell import build_config

    if args.allow_cpu:
        jax.config.update("jax_platforms", "cpu")
    backend.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = (backend.device_summary() if args.allow_cpu
              else backend.require_accelerator())

    manifest = Manifest(CHECKOUT)
    checks = {}
    for cell in manifest.data["workloads"]:
        if args.cells is not None and cell["name"] not in args.cells:
            continue
        plan = resolve_cell(manifest, cell["name"])
        cfg = build_config(plan)
        rows = cfg.learner.batch_size // plan["num_devices"]
        learner = dataclasses.replace(cfg.learner, batch_size=rows)
        key = (plan["config"], plan["reference"], learner, cfg.network,
               cfg.replay.prioritized)
        checks.setdefault(key, (cfg, rows, []))[2].append(cell["name"])

    out = {"device": device, "seeds": args.seeds, "studies": []}
    for (config, reference, *_), (cfg, rows, cells) in checks.items():
        env = make_jax_env(cfg.env_name)
        t0 = time.perf_counter()
        net = build_network(cfg.network, env.num_actions)
        module = manifest.reference(reference)
        check = reference_check.make_check(module, cfg, env, net, rows)
        results = [check(args.seed_base + i) for i in range(args.seeds)]
        control = []
        if args.control:
            coarse = reference_check.make_check(
                module, cfg, env, reference_check.CoarseNet(net), rows)
            control = [coarse(args.seed_base + i)
                       for i in range(args.control)]
        study = {
            "config": config, "rows": rows, "cells": cells,
            "compute_dtype": cfg.network.compute_dtype,
            "tolerances": results[0]["tolerances"],
            "all_ok": all(r["ok"] for r in results),
            "first_check_s": results[0]["seconds"],
            "median_check_s": median(r["seconds"] for r in results),
            "total_s": time.perf_counter() - t0,
            "errors": {k: [r["errors"][k] for r in results]
                       for k in results[0]["errors"]},
            "also": {k: [r["also"][k] for r in results]
                     for k in results[0]["also"]},
            "control_any_ok": any(r["ok"] for r in control),
            "control_errors": {k: [r["errors"][k] for r in control]
                               for k in results[0]["errors"]}}
        out["studies"].append(study)
        print(json.dumps({
            "config": config, "rows": rows, "cells": cells,
            "all_ok": study["all_ok"],
            "control_any_ok": study["control_any_ok"],
            "control_min_median_max": {
                k: [min(v), median(v), max(v)]
                for k, v in study["control_errors"].items() if v},
            "first_check_s": study["first_check_s"],
            "median_check_s": study["median_check_s"],
            "min_median_max": {
                k: [min(v), median(v), max(v)]
                for k, v in {**study["errors"], **study["also"]}.items()}}),
            flush=True)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
