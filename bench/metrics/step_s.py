"""Mean host-clock time of the window's train steps as the training loop
measures them (``train.run`` ``step_times``: batch, dispatch and the loss
on the host; checkpoint calls excluded)."""


def read(run):
    if not run.step_times:
        return None
    return sum(run.step_times) / len(run.step_times)
