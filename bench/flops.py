"""Operation and byte counts of the work the benchmark's cells require,
computed from shapes. Rooflines and utilisations divide these by time.

Counts are of the required work only: padded slots, recomputation and
the kernels' re-reads of a tile are not counted.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _conv(h, w, cin, cout, k):
    return 2 * h * w * cout * k * k * cin


def forward_flops(model: str) -> int:
    """Multiply-adds x 2 of one sample's forward pass."""
    if model == "mlp":                        # 784-200-10
        return 2 * 784 * 200 + 2 * 200 * 10
    if model == "cnn":                        # conv5x5(16), pool, conv5x5(32), pool, 1568-128-10
        return (_conv(28, 28, 1, 16, 5) + _conv(14, 14, 16, 32, 5)
                + 2 * 1568 * 128 + 2 * 128 * 10)
    raise KeyError(f"no FLOP count for model {model!r}")


def job_flops(model: str, processed: int, n_test: int, aggregations: int):
    """A training job's required FLOPs: forward and backward (3 x forward)
    on every processed sample, and one forward pass over the test set at
    every aggregation."""
    f = forward_flops(model)
    return 3 * f * processed + f * n_test * aggregations


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
