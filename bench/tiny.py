"""Tiny copies of the benchmark's cells, for driving the harness on the
CPU in tests: the same mode, traffic and CRAFT settings, at sizes a test
run can hold."""
from __future__ import annotations

import copy

from bench import harness

MODEL = dict(n_layers=2, d_model=64, vocab=512, n_heads=4, n_kv_heads=2,
             head_dim=16, d_ff=128)


def tiny_cell(name: str, **params) -> harness.Cell:
    cell = copy.deepcopy(harness.load_cell(name))
    model = cell.config["model"]
    model.update(MODEL)
    if model["window"]:
        model["window"] = 16
    cell.config["craft_env"]["CRAFT_CHUNK_BYTES"] = "4096"
    cell.traffic["seq_len"] = 32
    cell.params.update(global_batch=4, save_every=100)
    cell.params.update(params)
    return cell
