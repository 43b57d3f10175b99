"""Per resume: from the ``train.run`` call with a checkpoint present until
the loss of the first step after the restore is on the host; the mean over
the window's resumes (host clock)."""


def read(run):
    if not run.resumes:
        return None
    return sum(r["t_loss"] - r["t_call"] for r in run.resumes) / len(
        run.resumes)
