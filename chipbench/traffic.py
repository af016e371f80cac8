"""Traffic mixes and seeds.

A mix is a file of parameters, ``chipbench/traffic/<name>.json``, whose
``runner`` names the general runner in ``chipbench/runners/`` that reads
it. Every draw a runner makes from the run's seed goes through
``rng_for``, one stream per use, so the same seed gives the same inputs.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).with_name("traffic")


def load_traffic(name: str, traffic_dir: Path = TRAFFIC_DIR) -> dict:
    """The mix ``<traffic_dir>/<name>.json``."""
    path = Path(traffic_dir) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator of its own for each use of the seed; seeds of any size."""
    s = int(seed)
    return np.random.default_rng([s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF,
                                  int(s < 0), *stream.encode()])


def key_for(seed: int, stream: str) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey``, from any seed."""
    return int(rng_for(seed, stream).integers(0, 1 << 31))
