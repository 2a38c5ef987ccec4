"""Files of the benchmark found by the names ``BENCHMARK.json`` and the
data files give them: a traffic kind's driver (``drivers/<kind>.py``), a
value family's generator (``values/<family>.py``) and a per-layer
metric's reader (``metrics/<metric>.py``).  A new kind, family or metric
is a new file here, and no existing file changes."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent


def load(folder: str, name: str) -> ModuleType:
    """The module ``<folder>/<name>.py``, loaded once per process."""
    key = f"bench_{folder}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {folder[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
