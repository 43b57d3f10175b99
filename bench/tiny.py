"""Tiny copies of the benchmark's cells, for driving the harness on the
CPU in tests: the same mode, traffic and CRAFT settings, with the model
shrunk to its reference's ``TINY`` keys, at sizes a test run can hold."""
from __future__ import annotations

import copy
from pathlib import Path

from bench import harness


def tiny_cell(name: str, root: Path = harness.ROOT, **params) -> harness.Cell:
    cell = copy.deepcopy(harness.load_cell(name, root))
    model = cell.config["model"]
    tiny = harness.load_reference(cell.reference).TINY
    # a key the configuration leaves off (null or 0, as no window) stays so
    model.update({k: v for k, v in tiny.items() if model.get(k)})
    cell.config["craft_env"]["CRAFT_CHUNK_BYTES"] = "4096"
    cell.traffic["seq_len"] = 32
    cell.params.update(global_batch=4, save_every=100)
    cell.params.update(params)
    return cell
