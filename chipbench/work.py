"""The coded product's algorithmic work per worker, and per chip: the
roofline's numerator.

Worker k's coded task is ``sum_l w_kl A_{i_l}^T B_{j_l}`` over its live task
slots (``w_kl != 0``), followed by the decode combine of its (br, bt) result
into the mn output blocks.  The least work any implementation of that task
can do is counted here from the task table and A's tile pattern alone -- never
from a pack layout, a kernel grid or a slot order:

* flops: ``2 bs^2 bt`` for every live tile of A's column group ``i_l`` under
  every live slot, plus ``mn br bt`` for the decode combine;
* bytes: each live tile of the column groups the task touches, read once at
  its stored itemsize; each (row-block of B, column group ``j_l``) that those
  tiles multiply, ``bs x bt`` f32, read once; the worker's ``br x bt`` f32
  result written once.
"""

from __future__ import annotations

import numpy as np


def worker_work(cols, weights, m: int, n: int, tile_rows, *, s: int, r: int,
                t: int, block_size: int, tile_itemsize: int = 4) -> list[dict]:
    """[{"flops", "bytes", "tiles"}] for each worker of a task table.

    ``cols``/``weights`` are the (N, L) task table (block id ``i * n + j``,
    weight 0 on padded slots); ``tile_rows[cb]`` lists the row-blocks of
    A's live tiles in column-block ``cb`` (any order).
    """
    cols = np.asarray(cols)
    weights = np.asarray(weights)
    bs = block_size
    br, bt = r // m, t // n
    cbl = br // bs                     # column blocks of A per column group
    if r % m or t % n or br % bs or s % bs or len(tile_rows) != r // bs:
        raise ValueError("task table, tile pattern and sizes disagree")
    rows_of_group = [
        np.unique(np.concatenate(
            [np.asarray(tile_rows[cb], np.int64)
             for cb in range(i * cbl, (i + 1) * cbl)] + [np.zeros(0, np.int64)]))
        for i in range(m)]
    tiles_of_group = [sum(len(tile_rows[cb]) for cb in range(i * cbl, (i + 1) * cbl))
                      for i in range(m)]
    out = []
    for k in range(cols.shape[0]):
        live = [int(c) for c, w in zip(cols[k], weights[k]) if w != 0]
        groups_a = {c // n for c in live}
        b_blocks = set()
        for c in live:
            i, j = divmod(c, n)
            b_blocks.update((int(row), j) for row in rows_of_group[i])
        tiles = sum(tiles_of_group[c // n] for c in live)
        flops = 2.0 * bs * bs * bt * tiles + float(m * n * br * bt)
        nbytes = (sum(tiles_of_group[i] for i in groups_a) * bs * bs * tile_itemsize
                  + len(b_blocks) * bs * bt * 4 + br * bt * 4)
        out.append({"flops": float(flops), "bytes": float(nbytes), "tiles": tiles})
    return out


def chip_work(per_worker: list[dict], chips: int) -> dict:
    """Flops and bytes of one chip, mean over the chips: each chip's sum over
    the workers it holds.  ``P("model")`` splits the (N, ...) worker axis over
    the ``chips`` devices in contiguous runs of N / chips, so chip c holds
    workers c * N / chips to (c + 1) * N / chips - 1."""
    if len(per_worker) % chips:
        raise ValueError(f"{len(per_worker)} workers do not split over "
                         f"{chips} chips")
    return {key: float(np.mean(np.reshape([w[key] for w in per_worker],
                                          (chips, -1)).sum(axis=1)))
            for key in ("flops", "bytes")}
