"""`BENCHMARK.json` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

* configuration ``<c>``  -> the ``file`` its entry names, a JSON object
  whose ``family`` names ``families/<family>.py`` (generator and program
  glue) and ``reference/<family>.py`` (the plain reference);
* traffic ``<t>``        -> ``traffic/<t>.json``;
* per-layer metric ``<m>`` -> ``metrics/<m>.py``, a module with
  ``read(run) -> float | None``.

So a later change adds a cell, a configuration, a traffic mix or a
metric by adding files and entries, and edits no file that is here.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(ValueError):
    pass


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Benchmark:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, os.path.basename(HERE))
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))

    # -- lookups by name ---------------------------------------------------

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"unknown workload {name!r}; have "
                            f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise ManifestError(f"unknown configuration {name!r}")

    def traffic(self, name: str) -> Dict[str, Any]:
        return _load_json(os.path.join(self.dir, "traffic", f"{name}.json"))

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        """The cell's end-to-end metrics: those that list it, and those
        that list no cells at all."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """The per-layer metrics a traced run of the cell reports: those
        that list it, and those without a list whose ``moves`` the cell
        reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    def metric_reader(self, name: str):
        """The ``read`` function of ``metrics/<name>.py``."""
        path = os.path.join(self.dir, "metrics", f"{name}.py")
        if not NAME_RE.match(name) or not os.path.isfile(path):
            raise ManifestError(f"no reader for per-layer metric {name!r} "
                                f"(looked for {path})")
        spec = importlib.util.spec_from_file_location(
            f"perfbench.metrics.{name.replace('.', '__')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def family(config: Dict[str, Any]):
    """(generator module, reference module) of a configuration."""
    fam = config["family"]
    if not NAME_RE.match(fam):
        raise ManifestError(f"bad family name {fam!r}")
    return (importlib.import_module(f"perfbench.families.{fam}"),
            importlib.import_module(f"perfbench.reference.{fam}"))
