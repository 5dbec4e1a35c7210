"""What a cell is made of, found by name: its configuration file
(benchmark/configs/<name>.json), its traffic mix
(benchmark/traffic/<name>.json), the configuration's shape
(benchmark/shapes/<shape>.py, `"shape"` in the configuration, `plain`
where it names none), the mix's kind (benchmark/kinds/<kind>.py,
`"kind"` in the mix) and each per-layer metric's reader
(benchmark/metrics/<metric>.py).  A new cell adds such files and
BENCHMARK.json entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys


def _path(root: str, group: str, name: str, ext: str) -> str:
    return os.path.join(root, "benchmark", group, f"{name}{ext}")


def load_config(root: str, name: str) -> dict:
    path = _path(root, "configs", name, ".json")
    with open(path) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise ValueError(f"{path}: name {cfg.get('name')!r} != {name!r}")
    return cfg


def load_traffic(root: str, name: str) -> dict:
    path = _path(root, "traffic", name, ".json")
    with open(path) as f:
        t = json.load(f)
    if not os.path.isfile(_path(root, "kinds", str(t.get("kind")), ".py")):
        raise ValueError(f"{path}: unknown kind {t.get('kind')!r}")
    return t


def load(root: str, group: str, name: str):
    """The module benchmark/<group>/<name>.py of this checkout, loaded
    afresh (registered under its name, as a dataclass in it needs)."""
    path = _path(root, group, name, ".py")
    mod_name = f"bench_{group}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
