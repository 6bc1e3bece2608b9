"""What a cell is made of, found by the names in ``BENCHMARK.json``: the
configuration's file, the traffic mix's file, the queries the mix names and
the metric readers.  Nothing here knows a particular cell."""

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` by its path: a name may hold ``.`` and
    ``-``, which an import statement could not."""
    key = f"bench_{kind}_{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    module_spec = importlib.util.spec_from_file_location(key, path)
    if module_spec is None or not os.path.exists(path):
        raise SystemExit(f"no {kind} file for {name!r}: {path} is missing")
    module = importlib.util.module_from_spec(module_spec)
    sys.modules[key] = module
    module_spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, workload: str, root: str = ROOT):
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(
                f"unknown workload {workload!r}; BENCHMARK.json has "
                f"{sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = self.entry["chips"]
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config_name = conf["name"]
        self.config = load_json(os.path.join(root, conf["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.queries = {name: load_module("queries", name)
                        for name in sorted(self.traffic["mix"])}

    def metrics(self, group: str):
        """The (entry, reader module) pairs of ``end_to_end`` or
        ``per_layer`` that this cell reports."""
        out = []
        for m in self.bench[group]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            out.append((m, load_module("metrics", m["name"])))
        return out
