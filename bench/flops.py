"""Operation and byte counts of the work the benchmark's cells require,
computed from shapes. Rooflines and utilisations divide these by time.

Counts are of the required work only: padded slots, recomputation and
the kernels' re-reads of a tile are not counted.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def job_flops(config: dict, model, processed: int, aggregations: int):
    """A training job's required FLOPs: forward and backward (3 x forward)
    on every processed sample, and one forward pass over the configuration's
    test set at every aggregation. ``model`` is the configuration's model
    module, whose ``forward_flops(config)`` counts one sample's forward
    pass."""
    f = model.forward_flops(config)
    return 3 * f * processed + f * int(config["n_test"]) * aggregations


def peaks(device_kind: str, table: str = os.path.join(HERE, "peaks.json")
          ) -> dict:
    """Peak FLOP/s and bytes/s of one chip of this kind from the table
    ``table``; an unknown kind is an error, never a default."""
    with open(table) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
