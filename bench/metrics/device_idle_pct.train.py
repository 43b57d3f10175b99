"""Share of the traced window in which no operation ran on the device
(profiler trace: 1 - union of op intervals / window), in training cells."""


def read(run):
    if run.reduced is None or not run.step_times:
        return None
    return 100.0 * (1.0 - run.reduced["busy_s"] / run.reduced["window_s"])
