"""The benchmark's own library: everything under here is the yardstick."""

import importlib
import json


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_attr(name: str):
    """`module:function` -> that function of bench/lib/<module>.py."""
    module, _, attr = name.partition(":")
    return getattr(importlib.import_module(f"lib.{module}"), attr)
