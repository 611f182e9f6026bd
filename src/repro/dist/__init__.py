"""repro.dist — the distribution API fusing the FatPaths core with the
training stack.

Three modules, three layers of the same idea (spread one logical flow over
many near-disjoint physical paths):

* :mod:`repro.dist.sharding`    — ``Runtime``: the frozen mesh/layout
  contract every model/train/serve/data module programs against, plus the
  ``P`` partition-spec alias.  Degrades to single-device no-ops when
  ``mesh=None``.
* :mod:`repro.dist.collectives` — FatPaths-layered collective schedules as
  ``shard_map``/``ppermute`` programs: coprime-stride multi-ring
  all-reduce / reduce-scatter / all-gather (one collective-permute chain
  per ring == one routing layer per flowlet class).
* :mod:`repro.dist.fabric`      — ``ClusterFabric``: maps collective
  traffic onto :mod:`repro.core` topologies under minimal-path ECMP vs
  FatPaths layered routing and reports bottleneck bytes / time / link-load
  spread, so mesh placement and the roofline can quantify the paper's
  claim on this system's own traffic.

Importing any submodule applies :mod:`repro.dist.compat`'s CPU pin for
forced host devices.
"""

from . import compat  # noqa: F401  (pins the CPU under forced host devices)

compat.install()

from . import collectives, fabric, sharding  # noqa: E402,F401
from .collectives import (layer_strides, multiring_all_reduce,  # noqa: E402,F401
                          ring_all_gather, ring_reduce_scatter)
from .fabric import ClusterFabric, CollectiveReport, collective_flows  # noqa: E402,F401
from .sharding import P, Runtime, host_device_runtime  # noqa: E402,F401

__all__ = [
    "P",
    "Runtime",
    "host_device_runtime",
    "layer_strides",
    "multiring_all_reduce",
    "ring_reduce_scatter",
    "ring_all_gather",
    "ClusterFabric",
    "CollectiveReport",
    "collective_flows",
]
