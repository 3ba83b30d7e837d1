"""Model FLOP utilisation of the jitted train step of a latent-attention
expert model: the forward and backward FLOPs a step's tokens need
(``bench/flops_mla_moe.py``, from the configuration's shapes; the held
experts' share of the routed rows; recomputation not counted), over the
device time of a step in ``train_step`` (``train.step_device_ms``'s
programs) x chips x the bf16 peak of ``bench/peaks.json``."""
from bench.flops_mla_moe import train_flops_per_token

PROGRAM = r"train_step"


def read(run):
    s = run.trace.program_s(PROGRAM)
    if s is None:
        return None
    step_s = s / len(run.trace.steps)
    tokens = run.traffic["global_batch"] * run.traffic["seq_len"]
    flops = train_flops_per_token(run.config, run.traffic) * tokens
    peak = run.peaks["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops / (step_s * peak)
