"""Tokens of every step the window completed over the window's wall time,
with saves on at the cell's cadence (host clock)."""


def read(run):
    if not run.step_times:
        return None
    return len(run.step_times) * run.tokens_per_step / run.window_s
