"""Seconds one version took to land on the node tier (codec, files,
publish), the mean over the window's saves (span around
``Checkpoint._write_store_guarded``)."""

SLOT = "node"


def read(run):
    got = [t["seconds"] for t in run.tier_writes if t["slot"] == SLOT]
    if not got:
        return None
    return sum(got) / len(got)
