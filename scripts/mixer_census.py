"""Census of ONE state-space mixer (``models/sequence_core.py _Mamba2``)
compiled for v5e:2x2 from a machine with no chip: sizes and the compiler's
own cycle estimates, never times (``PERF.md`` §7.8c: 0.17-0.34 ns a cycle
across ops). Run from the sandbox:

    JAX_PLATFORMS=cpu python3 scripts/mixer_census.py [--shape 8 384 2688]
        [--top 40] [--passes forward grad]

``forward`` is the layer as a pass with no backward runs it; ``grad`` is
``jax.grad`` through ``jax.checkpoint``: forward, recomputed forward and
backward, as the learner runs a rematerialised layer. For each pass it
prints every top-level instruction of the optimised program (entry and
loop bodies, a loop's instructions times its trip count) with its output
and operand bytes and ``estimated_cycles``, largest first, and counts the
instructions that only MOVE an array — ``slice``, ``copy`` and ``broadcast``
writing over 10 MB, a stand-alone ``reduce`` reading as much (plain or as a
fusion that holds nothing else but converts and bitcasts) — and the float32
bytes written
between the two projections (every float32 output of at least 1 MB that
is no parameter's gradient and not the layer's output).
"""
import argparse
import json
import math
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

BIG = 10e6
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8}
#: opcodes that compute nothing: what a fusion may hold beside the one
#: moving opcode and still count as that movement
PASSIVE = {"parameter", "bitcast", "convert", "constant", "tuple",
           "get-tuple-element", "reshape", "transpose"}
MOVERS = ("slice", "copy", "broadcast", "reduce")
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def shape_bytes(text: str) -> int:
    """Logical bytes of every array shape spelled in ``text``."""
    return sum(ITEMSIZE[t] * math.prod(int(d) for d in dims.split(",") if d)
               for t, dims in _SHAPE.findall(text))


def computations(text: str):
    """``({name: [instruction lines]}, entry's name)`` of an HLO module."""
    comps, entry, current = {}, None, None
    for line in text.split("\n"):
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            current = head.group(2)
            comps[current] = []
            entry = current if head.group(1) else entry
        elif line.startswith("}"):
            current = None
        elif current is not None and " = " in line:
            comps[current].append(line)
    return comps, entry


def fusion_kind(lines) -> str:
    """What a fused computation does if it only moves an array: one of
    ``MOVERS`` where that opcode is all it holds beside ``PASSIVE`` ones."""
    opcodes = {m.group(3) for m in map(_INSTR.match, lines) if m}
    active = opcodes - PASSIVE
    if len(active) == 1 and next(iter(active)) in MOVERS:
        return next(iter(active))
    return ""


def census(text: str):
    """Rows ``{name, opcode, moves, out_bytes, operand_bytes, cycles,
    times, op_name}`` of every top-level instruction that does work."""
    comps, entry = computations(text)
    shapes = {}
    for lines in comps.values():
        for m in filter(None, map(_INSTR.match, lines)):
            shapes[m.group(1)] = m.group(2)
    rows = []

    def walk(name, times):
        for line in comps[name]:
            m = _INSTR.match(line)
            if not m:
                continue
            instr, shape, opcode, rest = m.groups()
            if opcode == "while":
                trips = re.search(r'"known_trip_count":\{"n":"(\d+)"', rest)
                walk(re.search(r"body=%([\w.\-]+)", rest).group(1),
                     times * (int(trips.group(1)) if trips else 1))
                continue
            if opcode in ("call", "conditional"):
                for target in re.findall(
                        r"(?:to_apply|branch_computations=\{|, )%([\w.\-]+)",
                        rest.split("metadata")[0]):
                    if target in comps:
                        walk(target, times)
                continue
            if opcode in PASSIVE - {"convert", "transpose"}:
                continue
            operands = rest.split("), ")[0] if "), " in rest else rest
            called = re.search(r"calls=%([\w.\-]+)", rest)
            moves = opcode if opcode in MOVERS else (
                fusion_kind(comps[called.group(1)]) if called else "")
            cycles = re.search(r'"estimated_cycles":"(\d+)"', rest)
            op_name = re.search(r'op_name="([^"]*)"', rest)
            rows.append({
                "name": instr, "opcode": opcode, "moves": moves,
                "shape": shape.split("{")[0],
                "out_bytes": shape_bytes(shape),
                "operand_bytes": sum(
                    shape_bytes(shapes.get(o, ""))
                    for o in re.findall(r"%([\w.\-]+)", operands)),
                "cycles": int(cycles.group(1)) if cycles else 0,
                "times": times,
                "op_name": op_name.group(1) if op_name else ""})

    walk(entry, 1)
    return rows


def summary(rows, kept_shapes):
    """Counts of moving instructions over ``BIG`` bytes and the float32
    bytes written between the projections. ``kept_shapes``: the shapes of
    the program's outputs (gradient leaves, the layer's output), which are
    not activations."""
    moving = {k: 0 for k in MOVERS}
    for r in rows:
        # a reduce is as large as what it reads, the others as what they write
        moved = r["operand_bytes" if r["moves"] == "reduce" else "out_bytes"]
        if r["moves"] and moved >= BIG:
            moving[r["moves"]] += r["times"]
    f32_between = sum(
        r["out_bytes"] * r["times"] for r in rows
        if r["shape"].startswith("f32") and r["out_bytes"] >= 1e6
        and r["shape"] not in kept_shapes)
    return {"moving_over_10MB": moving,
            "f32_written_between_projections_mb": round(f32_between / 1e6, 1),
            "estimated_mcycles": round(
                sum(r["cycles"] * r["times"] for r in rows) / 1e6, 3)}


def build(shape, passes):
    """``{pass: compiled}`` of one ``_Mamba2`` at the preset's widths over
    ``u [B, T, hidden]``, compiled for one described v5e chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.models import sequence_core

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = CONFIGS["twotower_q"].network
    core = cfg.core
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    B, T, hidden = shape
    H, P, N = core.mamba_num_heads, core.mamba_head_dim, core.ssm_state_size
    channels = H * P + 2 * core.n_groups * N
    module = sequence_core._Mamba2(core, jnp.dtype(cfg.compute_dtype))

    def described(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    u = jax.ShapeDtypeStruct((B, T, hidden), jnp.float32)
    seg = jax.ShapeDtypeStruct((B, T), jnp.int32)
    carry = (jax.ShapeDtypeStruct((B, core.conv_kernel - 1, channels),
                                  jnp.float32),
             jax.ShapeDtypeStruct((B, H, P, N), jnp.float32))
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), u, seg, carry)

    def loss(params, u, seg, carry):
        out, _ = jax.checkpoint(module.apply)(params, u, seg, carry)
        return jnp.sum(out * out)

    programs = {"forward": module.apply,
                "grad": jax.grad(loss, argnums=(0, 1))}
    args = described((params, u, seg, carry))
    return {name: jax.jit(programs[name]).lower(*args).compile()
            for name in passes}


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--shape", nargs=3, type=int, default=(8, 384, 2688),
                   metavar=("B", "T", "HIDDEN"))
    p.add_argument("--passes", nargs="+", default=("forward", "grad"),
                   choices=("forward", "grad"))
    p.add_argument("--top", type=int, default=40,
                   help="instructions printed a pass (0: all)")
    p.add_argument("--dump", default="",
                   help="directory to write each pass's optimised HLO to")
    args = p.parse_args(argv)

    import jax

    for name, compiled in build(tuple(args.shape), args.passes).items():
        text = compiled.as_text()
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            (Path(args.dump) / f"{name}.hlo").write_text(text)
        rows = census(text)
        # the program's own outputs are no activations: gradient leaves,
        # the layer's output (and u's gradient, of the same shape)
        kept = {"f32[" + ",".join(map(str, leaf.shape)) + "]"
                for leaf in jax.tree.leaves(compiled.out_info)}
        rows.sort(key=lambda r: -r["cycles"] * r["times"])
        for r in rows[:args.top or None]:
            print(f'{r["cycles"] * r["times"]:>10d} {r["times"]:>2d}x '
                  f'{r["moves"] or r["opcode"]:<10s} out '
                  f'{r["out_bytes"] / 1e6:8.1f} MB  in '
                  f'{r["operand_bytes"] / 1e6:8.1f} MB  {r["shape"]}  '
                  f'{r["name"]}  [{r["op_name"][-60:]}]')
        memory = compiled.memory_analysis()
        print(json.dumps(dict(
            summary(rows, kept), **{
                "pass": name, "shape": list(args.shape),
                "instructions": len(rows),
                "temp_gb": memory.temp_size_in_bytes / 1e9})))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
