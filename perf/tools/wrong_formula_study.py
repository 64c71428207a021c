"""What the comparison that decides ``correct`` reads when ONE formula of a
configuration's published mathematics is wrong, at the cell's own widths on
the chip: the readings a tolerance is set below, where the float8 control
(``reference_study.py --control``) gives it none.

    python3 perf/tools/wrong_formula_study.py --cell smallthinker_q.preset \
        --seed-base 7 --out chiprun_out/wrong_formulas.json

The cell's reference module lists its wrong formulas (``WRONG_FORMULAS``:
name -> (the function of the module it replaces, the wrong one)). For each,
the module computes with that one in place and the SOUND program is checked
against it through ``reference_check.make_check`` — the check a run of the
cell makes — on a seed of its own: what a program with that fault would read
against the sound reference. One line a formula as it is read.
"""
import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cell", required=True)
    p.add_argument("--formulas", nargs="*", default=None,
                   help="only these (default: all the module lists)")
    p.add_argument("--seed-base", type=int, default=0)
    p.add_argument("--out", type=Path, required=True)
    p.add_argument("--set", nargs="*", default=[], metavar="PATH=VALUE",
                   help="overrides over the cell's configuration")
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args()

    import jax

    from dist_dqn_tpu.config import apply_overrides
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
    plan = resolve_cell(manifest, args.cell)
    cfg = apply_overrides(build_config(plan), args.set)
    rows = cfg.learner.batch_size // plan["num_devices"]
    module = manifest.reference(plan["reference"])
    wrong = module.WRONG_FORMULAS
    names = list(wrong) if args.formulas is None else args.formulas
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)

    out = {"device": device, "cell": args.cell, "rows": rows,
           "overrides": args.set, "formulas": {}}
    for index, name in enumerate(names):
        replaced, formula = wrong[name]
        sound = getattr(module, replaced)
        setattr(module, replaced, formula)
        try:
            # traced anew: the reference's step looks its functions up by
            # name; the program's side is the sound one, from the cache
            result = reference_check.make_check(
                module, cfg, env, net, rows)(args.seed_base + index)
            read = {k: result[k] for k in ("ok", "errors", "tolerances",
                                           "also", "seconds")}
        except jax.errors.JaxRuntimeError as e:
            # the sound reference's step leaves 1.25 GB of a v5e's memory
            # (PERF.md §4): a wrong one may not fit; the others are still read
            read = {"refused": str(e).split("\n")[0]}
        finally:
            setattr(module, replaced, sound)
        out["formulas"][name] = dict(seed=args.seed_base + index,
                                     replaced=replaced, **read)
        print(json.dumps({name: out["formulas"][name]}), flush=True)
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
