"""Find a cell and its parts by the names in ``BENCHMARK.json``.

Nothing here names a cell, a configuration, an entry, a key distribution,
a reference or a metric: each is a file found by its name, so a later
change adds one by adding files.

- configuration ``<c>``:  the ``file`` that ``BENCHMARK.json`` gives it;
- cell ``<w>``:           ``bench/workloads/<w>.json`` (its traffic mix);
- entry ``<e>``:          ``bench/entries/<e>.py``, class ``Entry(dht,
  workload)`` with ``fields`` (the batch arrays a round takes),
  ``write_rows`` / ``read_rows`` (preload and read-back through the
  program), ``round`` (one timed round) and ``live`` (the state to wait on);
- key distribution:       ``bench/traffic/<d>.py``, function ``draw``;
- reference ``<r>``:      ``bench/references/<r>.py``, class ``Reference``;
- per-layer metric ``<m>``: ``bench/metrics/<m>.py``, function ``read``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's content
    workload: dict          # the cell's traffic-mix file
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]

    @property
    def table(self) -> dict:
        return self.config["table"]

    @property
    def n_ids(self) -> int:
        return int(self.config["records"])


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    """A per-layer metric is read in the cells its ``workloads`` lists, or
    else in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    workload = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    if workload.get("config") != w["config"] or \
            workload.get("traffic") != w["traffic"]:
        raise ValueError(f"bench/workloads/{name}.json names config "
                         f"{workload.get('config')!r} / traffic "
                         f"{workload.get('traffic')!r}, BENCHMARK.json "
                         f"{w['config']!r} / {w['traffic']!r}")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                workload=workload, end_to_end=e2e, per_layer=per_layer)


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]
