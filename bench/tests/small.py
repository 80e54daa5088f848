"""A cell's data at a size a test run on the CPU can hold."""

import spec


def cell_data(name: str, npx: int = 12, nk: int = 8):
    bench = spec.benchmark()
    cell = spec.cell(bench, name)
    cfg = dict(spec.config(bench, cell["config"]), npx=npx, nk=nk)
    return cell, cfg, spec.traffic(cell["traffic"]), spec.limits(name)


def cells():
    return [w["name"] for w in spec.benchmark()["workloads"]]
