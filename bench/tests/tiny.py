"""A cell of ``BENCHMARK.json`` cut to a size that the CPU runs in seconds:
the same configuration, traffic and limits, with small widths and batch."""

from __future__ import annotations

import dataclasses

from bench import cells


def tiny_cell(name: str) -> cells.Cell:
    cell = cells.load_cell(name)
    cfg = dict(cell.config, input_dim=32, backbone_widths=[32], projector_width=512)
    if cfg["reg"] == "sum":
        cfg["block_size"] = 32
    traffic = dict(cell.traffic, batch=16, log_every=5)
    if "pool" in traffic:
        traffic["pool"] = 4
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def cell_names() -> list[str]:
    return [w["name"] for w in cells.manifest()["workloads"]]
