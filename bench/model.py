"""A configuration file of ``bench/configs`` read into the sizes the
benchmark needs.  Its ``reference`` key names the configuration's family:
the module ``bench/reference/<family>.py`` reads the file, draws the
weights, holds the plain reference and counts the work, and
``bench/engines/<family>.py`` hands the weights to the program.  A family
that has neither is refused.  Imports nothing of the program."""

from __future__ import annotations

import importlib
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def _module(kind: str, name: str):
    path = f"bench.{kind}.{name}"
    try:
        return importlib.import_module(path)
    except ModuleNotFoundError as e:
        if e.name != path:
            raise
        raise ValueError(f"the benchmark has no {kind} module for the "
                         f"family {name!r} (bench/{kind}/{name}.py)") from None


def family(name: str):
    """The module of family ``name`` in ``bench/reference``."""
    return _module("reference", name)


def engine(name: str):
    """The module of family ``name`` in ``bench/engines``."""
    return _module("engines", name)


def load_spec(name: str, file: Path = None):
    """The configuration ``name``, from ``file`` (default: its file in
    ``bench/configs``), as its family reads it."""
    file = Path(file) if file else BENCH_DIR / "configs" / f"{name}.json"
    raw = json.loads(file.read_text())
    return family(raw["reference"]).load_spec(name, raw)
