"""The one traffic generator: every mix is a data file,
``bench/traffic/<mix>.json``, read by :func:`load`.

A mix drives the topology of the cell's configuration through the
session backend ``backend``, by the entry ``entry`` names
(``bench/harness.py``): ``session`` (the default), back-to-back
``MinCutSession.solve`` calls, or ``server``, ``clients`` closed-loop
clients of ``MinCutServer.submit``, with the server settings the file
names.  Each request is a fresh instance of the configuration's family,
as the paper's Table 3 times its solves: the terminals are drawn anew
(``instances.draw``), the edge weights are the configuration's.  It is
solved cold, unless the mix has ``tenants``: then each client is a tenant
and warm-starts from its last answer.  ``pool`` instances are drawn in
set-up from ``(seed, k)``, so one seed gives the same instances in every
run; the window takes them in turn, and from the first again once it has
taken them all.  One more, ``k = pool``, warms the programs up.
"""
from __future__ import annotations

import json
import os
from typing import List

from bench import instances


def load(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if int(mix.get("pool", 0)) < 1:
        raise ValueError(f"traffic {name!r}: pool must be at least 1")
    return mix


def pool(spec: dict, topo, mix: dict, seed: int) -> List[instances.Instance]:
    """The window's instances, then the warm-up's, drawn from ``seed``."""
    return [instances.draw(spec, topo, [int(seed), k])
            for k in range(int(mix["pool"]) + 1)]
