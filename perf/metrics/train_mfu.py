"""Train step: FLOPs a grad step requires (counted from shapes by the
configuration's reference module, ``grad_step_flops(cfg, env)``; the run's
record carries the number) x grad steps per second, over chips x the bf16
peak. An end-to-end utilisation of the learner's arithmetic, not a kernel's
roofline share."""
from perf.reduce.peaks import peak


def read(run, trace):
    return 100.0 * run["grad_step_flops"] * run["rates"]["grad_steps_per_s"] / (
        run["chips"] * peak(run["device"]["kind"], "bf16_flops"))
