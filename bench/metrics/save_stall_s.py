"""Wall time the step loop spent inside ``update_and_write`` during the
window (saving or not), per save in the window (host clock)."""


def read(run):
    if not run.saves:
        return None
    return run.update_seconds / len(run.saves)
