"""A cell of ``BENCHMARK.json`` cut to a size that the CPU runs in seconds:
the same configuration, traffic and limits, with the small widths and batch
that the cell's program module gives (its ``tiny``)."""

from __future__ import annotations

import dataclasses

from bench import cells


def tiny_cell(name: str) -> cells.Cell:
    cell = cells.load_cell(name)
    cfg, traffic = cells.program(cell).tiny(cell.config, cell.traffic)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def cell_names() -> list[str]:
    return [w["name"] for w in cells.manifest()["workloads"]]
