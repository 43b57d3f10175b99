"""Seconds each resume of the window spent reading the version back
(``Checkpoint.stats["read_seconds"]``: read, decode and verify, assemble,
place on the device), the mean over the window's resumes."""


def read(run):
    if not run.resumes:
        return None
    return sum(r["read_s"] for r in run.resumes) / len(run.resumes)
