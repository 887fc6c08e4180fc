"""Architectures the harness can run, one module each.

A configuration file names its architecture under ``"arch"``;
``bench/arch/<arch>.py`` holds everything the harness knows of that
layout, and the harness's own modules dispatch to it:

- ``program_config(c)``: the program's ``ModelConfig`` for the file;
- ``shapes(c)``: the weight tree in the program's layout, each leaf a
  shape tuple, and ``init(name, shape, key, c)``: the float32 leaf at
  ``name`` (its path joined by ``/``) drawn from ``key``;
- ``train_flops_per_token(c, seq)`` and ``decode_step(c, positions)``:
  the operations and bytes the algorithm needs, with their sources;
- ``matmul_params(c)`` and ``kv_entry_bytes(c)``: the counts those use;
- ``reference``: the plain float32 reference module (``logits``,
  ``row_loss``), which imports nothing of the program.
"""
from __future__ import annotations

import importlib
import os
from types import ModuleType
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def known() -> List[str]:
    return sorted(f[:-3] for f in os.listdir(HERE)
                  if f.endswith(".py") and not f.startswith("_"))


def of(c: Dict[str, Any]) -> ModuleType:
    """The module of the architecture configuration ``c`` names."""
    name = c.get("arch")
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise KeyError(f"configuration {c.get('name')!r} names arch {name!r}; "
                       f"known: {known()}") from None
