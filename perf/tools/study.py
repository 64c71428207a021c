"""The noise study, one command: N fresh-process runs of each cell, every
run's record (last line and per-chunk series) kept, then the table.

    python3 perf/tools/study.py --out chiprun_out/study/set1 \
        --runs atari.preset:6 atari.learn8:3 apex.preset:3 --traced 1

    python3 perf/tools/study.py --summarize perf/records/pr23/set1 \
        perf/records/pr23/set2 perf/records/pr23/set3 --as-window 15

This parent never touches JAX (a chip belongs to one process); the runs are
sequential children. A run that had to compile (persistent-cache misses > 0:
the first of a cell in a checkout) is marked ``first``. No run is dropped: a failed run is kept with its exit code.
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path
from statistics import median

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))
# A run must end within the contract's limit for a compiling run; a hung
# child must not hold the chip until the whole call's time limit.
RUN_TIMEOUT_S = 900
END_TO_END = ("env_steps_per_s_chip", "grad_steps_per_s", "hbm_peak_gb",
              "setup_s")


def run_set(out: Path, runs, traced: int, seconds, seed_base: int) -> None:
    out.mkdir(parents=True, exist_ok=True)
    for spec in runs:
        cell, _, count = spec.partition(":")
        plan = [(0, i) for i in range(int(count or 1))]
        plan += [(1, 1000 + i) for i in range(traced)]
        for trace, i in plan:
            seed = seed_base + i
            record = out / f"{cell}.seed{seed}.trace{trace}.json"
            cmd = [sys.executable, str(CHECKOUT / "perf/run.py"),
                   "--workload", cell, "--seed", str(seed),
                   "--trace", str(trace), "--record", str(record)]
            if seconds:
                cmd += ["--seconds", str(seconds)]
            t0 = time.time()
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      cwd=CHECKOUT, timeout=RUN_TIMEOUT_S)
            except subprocess.TimeoutExpired as e:
                proc = subprocess.CompletedProcess(
                    cmd, 124, stdout="", stderr=f"timeout: {e}")
            note = {"cell": cell, "seed": seed, "trace": trace,
                    "first": None, "rc": proc.returncode,
                    "process_s": time.time() - t0}
            if record.is_file():
                note.update(json.loads(record.read_text()))
                # "first" means: this run compiled (the machine may have
                # come with a compile cache from an earlier call).
                note["first"] = note["compile"]["total"]["cache_misses"] > 0
            else:
                note["stderr_tail"] = proc.stderr[-3000:]
            record.write_text(json.dumps(note))
            last = (proc.stdout.strip().splitlines() or ["<no output>"])[-1]
            print(json.dumps({k: note.get(k) for k in (
                "cell", "seed", "trace", "first", "rc", "process_s",
                "setup_s")}), last[:600], flush=True)
            if proc.returncode:
                print(proc.stderr[-2000:], flush=True)


def _stats(values):
    values = sorted(values)
    n = len(values)
    if not n:
        return None
    med = median(values)
    q1 = median(values[:n // 2]) if n > 1 else med
    q3 = median(values[(n + 1) // 2:]) if n > 1 else med
    return {"n": n, "median": med, "min": values[0], "max": values[-1],
            "range_over_median": (values[-1] - values[0]) / med,
            "iqr_over_median": (q3 - q1) / med}


def reduce_record(r: dict, as_window: float = 0.0) -> dict:
    """One run's end-to-end values and host-loop readings, from its kept
    per-chunk series under the estimator as it is now. With ``as_window``
    the series is read as a window of that many seconds: its chunks up to
    the first boundary at or after it — what a run of that length records,
    the process being the same up to there."""
    from perf.harness.estimator import host_loop_summary, median_rate

    s = r["series"]
    n = len(s["cycle_s"])
    if as_window:
        elapsed = 0.0
        for n, cycle in enumerate(s["cycle_s"], 1):
            elapsed += cycle
            if elapsed >= as_window:
                break
    cycles, walls = s["cycle_s"][:n], s["wall_s"][:n]
    return {
        "seed": r["seed"], "first": r["first"], "chunks": n,
        "env_steps_per_s_chip":
            median_rate(cycles, s["frames"][:n]) / r["chips"],
        "grad_steps_per_s": median_rate(cycles, s["grad_steps"][:n]),
        "hbm_peak_gb": r["memory_stats"]["peak_bytes_in_use"] / 1e9,
        "setup_s": r["setup_s"],
        "slowest_cycle_over_median": max(cycles) / median(cycles),
        "slowest_wall_over_median": max(walls) / median(walls),
        **host_loop_summary(cycles, walls, s["frames"][:n])}


def summarize(dirs, as_window: float = 0.0) -> dict:
    """Per set, cell and end-to-end metric: n, median, min, max, range over
    median, interquartile distance over median (the driver's spread). Set-up
    leaves out each cell's first (compiling) run and reports it apart."""
    table = {}
    for d in dirs:
        by_cell = {}
        for path in sorted(Path(d).glob("*.trace0.json")):
            r = json.loads(path.read_text())
            by_cell.setdefault(r["cell"], []).append(r)
        for cell, records in by_cell.items():
            rows = [reduce_record(r, as_window) for r in records
                    if r.get("rc") == 0]
            for metric in END_TO_END:
                values = [row[metric] for row in rows
                          if not (metric == "setup_s" and row["first"])]
                table.setdefault(cell, {}).setdefault(metric, {})[
                    Path(d).name] = _stats(values)
            table[cell].setdefault("first_setup_s", {})[Path(d).name] = [
                row["setup_s"] for row in rows if row["first"]]
            table[cell].setdefault("failed_runs", {})[Path(d).name] = sum(
                1 for r in records if r.get("rc") != 0)
            table[cell].setdefault("runs", {})[Path(d).name] = rows
    return table


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", type=Path)
    p.add_argument("--runs", nargs="*", default=[])
    p.add_argument("--traced", type=int, default=0,
                   help="traced runs per cell, after the untraced ones")
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--seed-base", type=int, default=100)
    p.add_argument("--summarize", nargs="*", default=None)
    p.add_argument("--as-window", type=float, default=0.0,
                   help="with --summarize: read every kept series as a "
                        "window of this many seconds")
    args = p.parse_args()
    if args.summarize is not None:
        print(json.dumps(summarize(args.summarize, args.as_window),
                         indent=1))
        return 0
    run_set(args.out, args.runs, args.traced, args.seconds, args.seed_base)
    print(json.dumps(summarize([args.out]), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
