"""Model FLOPs utilization of the traced window: forward and backward
FLOPs per token (the configuration's reference, ``model_flops_per_token``,
no recompute) times tokens per second, over the chip's bf16 peak
(``bench/peaks.py``)."""
from bench import peaks


def read(run):
    if not run.step_times:
        return None
    tokens_per_s = len(run.step_times) * run.tokens_per_step / run.window_s
    per_token = run.reference.model_flops_per_token(run.model, run.seq_len)
    peak = peaks.peaks_for(run.device_kind)["bf16_flops"] * run.cell.chips
    return 100.0 * per_token * tokens_per_s / peak
