"""Pallas segment-reduce kernels (sum / max) for the sparse network
plane.

The O(E) plane replaces dense (n, n) reductions with reductions over
edge lists: per-device gather of incoming shares (sum of plan-edge
volumes grouped by receiver) and H-weighted aggregation over an active
device list. Both are segment reductions ``out[s] = op over
data[segment_ids == s]``.

Kernel shape: elements are padded/reshaped to (rows, CHUNK) and read
ROWS rows — one (8, 128) f32 tile — per grid step; segments are cut
into tiles of BS. The grid is (segment tiles × element blocks) with the
block axis ``arbitrary``, so each tile's (BS, CHUNK) VMEM accumulator
is revisited and folded in place (same discipline as
``offload_greedy``'s column sweep). Each row of a step builds the
one-hot membership ``hit[s, c] = (ids[c] == tile_base + s)`` and folds
the selected values into the accumulator elementwise (add for sum, max
for max); the tile's last step transposes the accumulator so segments
run along lanes and reduces it to the (1, BS) output row. Segment ids
need NOT be sorted. The work is E·⌈S/BS⌉ element visits, so the
dispatch in ``kernels.ops`` bounds S.

Empty segments match the jnp fallback identities (``jax.ops``):
0 for sum, −inf for max. On CPU the kernel runs in interpret mode;
``kernels.ops.segment_sum`` / ``segment_max`` pick the jnp fallback
off-accelerator, below ``PALLAS_MIN_N`` elements and above
``PALLAS_MAX_SEGMENTS`` segments.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BS = 128      # segment tile (lane dimension of the output)
CHUNK = 128   # elements per row (lane dimension of the inputs)
ROWS = 8      # rows per grid step: one (8, 128) tile of ids and data


def _seg_kernel(ids_ref, data_ref, out_ref, acc_ref, *, op: str):
    cj = pl.program_id(1)
    ident = 0.0 if op == "sum" else -jnp.inf

    @pl.when(cj == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ident)

    seg = (pl.program_id(0) * BS
           + jax.lax.broadcasted_iota(jnp.int32, (BS, CHUNK), 0))
    acc = acc_ref[...]
    for r in range(ROWS):
        hit = seg == ids_ref[r:r + 1, :]                  # (BS, CHUNK)
        sel = jnp.where(hit, data_ref[r:r + 1, :], ident)
        acc = acc + sel if op == "sum" else jnp.maximum(acc, sel)
    acc_ref[...] = acc

    @pl.when(cj == pl.num_programs(1) - 1)
    def _finalize():
        lanes = acc_ref[...].T                            # (CHUNK, BS)
        if op == "sum":
            out_ref[...] = lanes.sum(axis=0, keepdims=True)
        else:
            out_ref[...] = lanes.max(axis=0, keepdims=True)


def _segment_reduce(data, segment_ids, num_segments: int, op: str,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    E = data.shape[0]
    block = ROWS * CHUNK
    nblocks = max(1, -(-E // block))
    ntiles = max(1, -(-num_segments // BS))
    epad = nblocks * block - E
    # padded elements point one past the last segment tile: they match
    # no output row, so padding contributes the identity
    ids = jnp.concatenate([
        jnp.asarray(segment_ids, jnp.int32),
        jnp.full((epad,), ntiles * BS, jnp.int32)]).reshape(-1, CHUNK)
    vals = jnp.concatenate([
        jnp.asarray(data, jnp.float32),
        jnp.zeros((epad,), jnp.float32)]).reshape(-1, CHUNK)
    out = pl.pallas_call(
        partial(_seg_kernel, op=op),
        grid=(ntiles, nblocks),
        in_specs=[
            pl.BlockSpec((ROWS, CHUNK), lambda si, cj: (cj, 0)),
            pl.BlockSpec((ROWS, CHUNK), lambda si, cj: (cj, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, BS), lambda si, cj: (si, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ntiles, 1, BS), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BS, CHUNK), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, vals)
    return out.reshape(-1)[:num_segments]


def segment_sum_pallas(data, segment_ids, num_segments: int, *,
                       interpret: bool | None = None):
    """out[s] = Σ data[segment_ids == s]; empty segments give 0."""
    return _segment_reduce(data, segment_ids, num_segments, "sum",
                           interpret)


def segment_max_pallas(data, segment_ids, num_segments: int, *,
                       interpret: bool | None = None):
    """out[s] = max data[segment_ids == s]; empty segments give −inf."""
    return _segment_reduce(data, segment_ids, num_segments, "max",
                           interpret)
