"""Train step: FLOPs a grad step requires (from shapes) x grad steps per
second, over chips x the bf16 peak. An end-to-end utilisation of the
learner's arithmetic, not a kernel's roofline share."""
from perf.reduce.flops import grad_step_flops
from perf.reduce.peaks import peak


def read(run, trace):
    flops = grad_step_flops(run["batch_size"], obs_shape=run["obs_shape"],
                            hidden=run["hidden"],
                            num_actions=run["num_actions"],
                            dueling=run["dueling"],
                            double_dqn=run["double_dqn"])
    return 100.0 * flops * run["rates"]["grad_steps_per_s"] / (
        run["chips"] * peak(run["device"]["kind"], "bf16_flops"))
