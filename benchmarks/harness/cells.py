"""Finding a cell's files by the names in the manifest.

Nothing here lists cells, configurations, mixes or metrics: a name in
``BENCHMARK.json`` (or, for a rehearsal, in the manifest given with
``--manifest``) is turned into a path under the benchmark's own directory
and the file there is loaded.  A later PR adds files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)


class CellError(Exception):
    """The manifest or one of the files it names is missing or wrong."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"cannot read {path}: {e}") from e


def load_module(path: str):
    """Import one file by path (its name may hold dots and dashes)."""
    if not os.path.isfile(path):
        raise CellError(f"no such file: {path}")
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, manifest: dict, base: str, workload: str):
        self.manifest = manifest
        self.base = base          # directory the manifest's files hang off
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if workload not in by_name:
            raise CellError(f"no workload {workload!r} in the manifest; it "
                            f"has {sorted(by_name)}")
        self.entry = by_name[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in manifest["configs"]}.get(
            self.entry["config"])
        if cfg is None:
            raise CellError(f"workload {workload!r} names the configuration "
                            f"{self.entry['config']!r}, which is not listed")
        self.config = load_json(os.path.join(REPO, cfg["file"]))
        self.traffic = load_json(os.path.join(
            base, "traffic", self.entry["traffic"] + ".json"))
        if self.traffic.get("kind") not in ("train", "serve"):
            raise CellError(f"traffic {self.entry['traffic']!r} must say "
                            "kind: train or serve")
        self.kind = self.traffic["kind"]
        # limits of `correct`, set per cell from readings (PERF.md s2)
        self.limits = load_json(os.path.join(
            base, "cells", workload + ".json"))["limits"]
        self._loaded: dict = {}

    def _reports(self, metric: dict, e2e_names: Optional[List[str]] = None
                 ) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return e2e_names is None or metric["moves"] in e2e_names

    def end_to_end(self) -> List[dict]:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> List[dict]:
        e2e = [m["name"] for m in self.end_to_end()]
        return [m for m in self.manifest["per_layer"]
                if self._reports(m, e2e)]

    def generator(self):
        return load_module(os.path.join(
            ROOT, "generators", self.traffic["generator"] + ".py"))

    def driver(self):
        return load_module(os.path.join(ROOT, "drivers", self.kind + ".py"))

    def _own(self, folder: str, name: str):
        """``<folder>/<name>.py``, under the manifest's base directory
        where it has one (a rehearsal may bring a family or a reader of
        its own) and under ``benchmarks/`` otherwise; loaded once."""
        key = (folder, name)
        if key not in self._loaded:
            paths = [os.path.join(root, folder, name + ".py")
                     for root in (self.base, ROOT)]
            found = [p for p in paths if os.path.isfile(p)]
            if not found:
                raise CellError(f"no such file: {' nor '.join(paths)}")
            self._loaded[key] = load_module(found[0])
        return self._loaded[key]

    def family(self):
        """What the benchmark knows of the model's family
        (``families/<family>.py``; its questions are in the README)."""
        return self._own("families", self.config["family"])

    def reference(self):
        return self._own("references", self.config["family"])

    def layer_metric(self, name: str):
        return self._own("layer_metrics", name)


def kernel(name: str):
    """``kernels/<name>.py``: operations and bytes from shapes."""
    return load_module(os.path.join(ROOT, "kernels", name + ".py"))


def load_manifest(path: Optional[str]) -> tuple:
    """(manifest, base directory, is_rehearsal).  Without ``path`` it is
    the repo's ``BENCHMARK.json`` and the files hang off ``benchmarks/``."""
    real = load_json(os.path.join(REPO, "BENCHMARK.json"))
    if path is None:
        return real, ROOT, False
    m = load_json(path)
    clash = {w["name"] for w in m["workloads"]} & \
        {w["name"] for w in real["workloads"]}
    if clash:
        raise CellError(f"a rehearsal manifest may not name a cell of "
                        f"BENCHMARK.json: {sorted(clash)}")
    return m, os.path.dirname(os.path.abspath(path)), True
