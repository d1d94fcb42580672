"""Tiny cells for the CPU tests: the real configurations and traffic mixes
with their sizes cut to what the Pallas interpreter runs in seconds."""
from __future__ import annotations

import dataclasses

from harness import spec

TINY = {
    "resnet20-k16": dict(
        width=8, num_agents=4, batch_size=8, image_size=8,
        min_samples_per_agent=96, max_samples_per_agent=112,
    ),
    "danube3-4b-1l-k2": dict(
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        vocab=128, window=8, seq_len=16,
    ),
}
LOOSE = {"loss_gap": 1.0, "momentum_gap": 1.0, "change_gap": 1.0}


def tiny_cell(name: str, limits: dict | None = None, **traffic) -> spec.Cell:
    """The cell ``name`` (``<config>.<traffic>``) at its tiny size, with
    ``traffic`` overriding keys of its traffic mix."""
    cell = spec.find_cell(name)
    config = dict(cell.config, **TINY[name.rsplit(".", 1)[0]])
    return dataclasses.replace(
        cell, config=config, traffic=dict(cell.traffic, **traffic), limits=limits or LOOSE
    )
