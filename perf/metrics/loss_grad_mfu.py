"""Train step: FLOPs a grad step requires (from shapes, as ``train_mfu``)
over the device time of stage ``loss_grad`` alone x chips x the bf16 peak:
what the matmul part reaches once sampling, gathering, the optimizer and the
acting half of the chunk are taken out of the denominator."""
from perf.metrics import _stages
from perf.reduce.peaks import peak


def read(run, trace):
    ms = _stages.ms_per_grad_step(run, trace, "loss_grad")
    if not ms:
        return None
    return 100.0 * run["grad_step_flops"] / (
        1e-3 * ms * run["chips"] * peak(run["device"]["kind"], "bf16_flops"))
