"""Per save of the window: from its ``update_and_write`` call until the
version is published on every tier it was scheduled for; the mean over the
window's saves, the last one included (host clock)."""


def read(run):
    if not run.saves or any(s["t_durable"] is None for s in run.saves):
        return None
    return sum(s["t_durable"] - s["t_call"] for s in run.saves) / len(
        run.saves)
