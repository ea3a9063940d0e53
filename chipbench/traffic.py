"""Traffic: a data file of parameters -> the cell's inputs, by the generator
the file names.

A training job's traffic is its table.  ``traffic/<name>.json`` holds the
parameters, among them ``generator``: the module of
``chipbench/generators/`` that makes the inputs from them and the seed
(that package's docstring states what a generator provides).  A new kind of
traffic is a new generator module and a new parameter file; nothing here
names one.
"""

from __future__ import annotations

import importlib
import json
import os
import pkgutil
from types import ModuleType
from typing import Any, Dict, NamedTuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Table(NamedTuple):
    x: np.ndarray       # (rows, width) float32
    y: np.ndarray       # (rows,): what it holds is the generator's to say


def load(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def generator(params: Dict[str, Any]) -> ModuleType:
    """The module ``chipbench.generators.<params["generator"]>``."""
    from . import generators

    name = params.get("generator")
    found = sorted(m.name for m in pkgutil.iter_modules(generators.__path__))
    if name not in found:
        raise ValueError(f"unknown generator {name!r}; chipbench/generators "
                         f"holds {found}")
    return importlib.import_module(f"{generators.__name__}.{name}")


def generate(params: Dict[str, Any], seed: int) -> Any:
    return generator(params).generate(params, seed)


def width(params: Dict[str, Any]) -> int:
    return generator(params).width(params)
