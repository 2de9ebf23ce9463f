"""Shared helpers for the scripts in this package."""

from __future__ import annotations

import json
import os
import time


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def write_artifact(name: str, payload: dict) -> str:
    """Write a timestamped benchmark artifact at the repo root."""
    path = os.path.join(repo_root(), name)
    with open(path, "w") as f:
        json.dump({"ts": time.strftime("%Y-%m-%d %H:%M"), **payload}, f,
                  indent=1)
    return path


def merge_artifact(name: str, section: str, payload) -> str:
    """Write ONE top-level section of a shared artifact, preserving every
    other section: SERVE_BENCH.json is shared by
    serve_bench's baseline ``results`` and serve_shard_bench's ``sharded``
    section — a rerun of either must not clobber the other."""
    path = os.path.join(repo_root(), name)
    prior = {}
    try:
        with open(path) as f:
            prior = json.load(f)
    except (OSError, ValueError):
        pass
    prior.pop("ts", None)
    prior[section] = payload
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"ts": time.strftime("%Y-%m-%d %H:%M"), **prior}, f,
                  indent=1)
    os.replace(tmp, path)
    return path
