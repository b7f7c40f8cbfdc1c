"""Where a cell's files are. Everything is found by the names in
``BENCHMARK.json``: nothing here knows a cell, a configuration, a mix or a
metric by name.

* configuration  ``<name>`` -> its ``file`` in ``BENCHMARK.json``
* traffic mix    ``<name>`` -> ``benchmark/traffic/<name>.json``
* cell           ``<name>`` -> ``benchmark/cells/<name>.json`` (the limits
  of its output check, with the readings they were set from)
* per-layer metric ``<name>`` -> ``benchmark/layer_metrics/<name>.py``
* a product (serve, train) -> ``benchmark/harness/<product>.py``, chosen by
  the configuration file's ``"product"``
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file of the benchmark by path (metric readers and hooks
    have names, such as ``x.chat``, that are no module names)."""
    full = path if os.path.isabs(path) else os.path.join(ROOT, path)
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in os.path.relpath(full, ROOT))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, full)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, bench: dict = None, overrides: dict = None,
                 data_dir: str = BENCH):
        """``bench`` and ``data_dir`` let the tests under
        ``benchmark/tests`` run tiny cells of their own; ``overrides`` is
        merged into the configuration (the control's lower precision)."""
        self.bench = bench or _load(os.path.join(ROOT, "BENCHMARK.json"))
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.row = rows[0]
        self.name, self.chips = name, int(self.row["chips"])
        cfg_row = next(c for c in self.bench["configs"]
                       if c["name"] == self.row["config"])
        self.config = _load(os.path.join(ROOT, cfg_row["file"]))
        self.mix = _load(os.path.join(data_dir, "traffic",
                                      self.row["traffic"] + ".json"))
        self.limits = _load(os.path.join(data_dir, "cells", name + ".json"))
        for section, values in (overrides or {}).items():
            _merge(self.config, {section: values})

    def reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self.reports(m)]

    def reader(self, metric_name: str):
        return load_module(os.path.join(BENCH, "layer_metrics",
                                        metric_name + ".py")).read

    def product(self):
        """The module that runs this cell's product (serve, train)."""
        return importlib.import_module(
            "benchmark.harness." + self.config["product"])

    def hook(self, which: str):
        return load_module(self.config["hooks"][which])


def _merge(into: dict, patch: dict) -> None:
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            _merge(into[k], v)
        else:
            into[k] = v


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(
            f"benchmark: no peaks for device kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
