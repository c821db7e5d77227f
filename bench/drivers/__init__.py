"""The general drivers that turn a configuration and a traffic mix into a run.

A mix file (``bench/mixes/<traffic>.json``) is data: its ``driver`` names a
module here (``bench/drivers/<driver>.py``, exposing ``DRIVER``) and the
rest are its parameters. A configuration's ``dataset`` and ``partitioner``
name modules of ``bench/datasets`` and ``bench/partitioners`` the same way,
so a new configuration, mix or traffic kind is a new file. Every driver

* builds the configuration's graph with its dataset module (from the
  configuration's ``graph_seed``: a deployment serves one dataset, and
  every run then compiles the same shapes) and hands it to
  ``PartitionedGraphService`` on a mesh of the run's chips, partitioned by
  the configuration's partitioner;
* warms up every shape its window uses (``setup``);
* measures whole units of work until ``seconds`` have passed (``window``),
  recording host spans around the calls into each layer;
* frees the program's state (``release``) and compares what the window
  produced with the plain reference (``check``). ``plant_control`` first
  puts the control in the program's place, so that ``check`` judges it.

Seeds: traffic, dynamism, the initial partition and the check's sample
come from ``--seed``; every stream is ``SeedSequence([seed, <stream>])``, so
any whole number is a valid seed and the streams of one seed never overlap.
"""

from __future__ import annotations

import importlib


def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    return importlib.import_module(f"bench.{kind}.{name}")


def make(config: dict, mix: dict, seed: int, spans, devices, seconds: float = None):
    driver = plugin("drivers", mix["driver"]).DRIVER(config, mix, seed, spans, devices)
    driver.window_seconds = seconds
    return driver
