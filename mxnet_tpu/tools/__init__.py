"""Tooling (reference: tools/ — im2rec, launch.py, bandwidth,
parse_log, diagnose, flakiness_checker, kill-mxnet, amalgamation).

Every submodule here is a ``python -m mxnet_tpu.tools.<name>`` CLI entry
point, so NONE are imported eagerly — an eager import would already be
in sys.modules when runpy executes the same module, tripping its
double-import RuntimeWarning. ``mx.tools.<name>`` attribute access still
works via lazy module __getattr__ (PEP 562).
"""
from __future__ import annotations

import importlib

_SUBMODULES = ("im2rec", "launch", "bandwidth", "parse_log", "diagnose",
               "flakiness_checker", "kill_mxnet", "amalgamate")

__all__ = list(_SUBMODULES)


def __getattr__(name):
    if name in _SUBMODULES:
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod  # cache: next access skips __getattr__
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
