"""The one traffic generator: operands and schedules from the files of a cell.

A's density belongs to the configuration (``configs/<name>.json``): ``nnz_a``
nonzeros drawn uniformly over the (s, r) matrix, as the paper draws them.
The device path holds A in ``block_size`` x ``block_size`` tiles, so a tile
is live where at least one nonzero falls into it (``live_tile_fraction``),
and a live tile is dense.  Every column block of A holds the same number of
live tiles, at row positions drawn uniformly, so every seed stages the same
shapes and the same work.

A traffic file (``traffic/<name>.json``) holds the loop's parameters only;
any other key is refused:

* ``b_operands`` -- how many dense B operands the closed loop cycles through;
* ``warmup_products`` -- how many products set-up runs before the window
  (``Schedule.warmup``);
* ``membership`` (optional) -- ``dead_workers``: how many workers straggle
  in each product, drawn from the seed uniformly among all the workers, anew
  for every product, as the paper's Section V experiments pick their
  stragglers per job.  C is decoded from the other workers where they keep
  the code decodable, and from every worker where they do not: the master
  then has to wait for the straggler.

Set-up's (B operand, mask) pairs are every B operand unmasked, then each
decodable mask under the first operand (``Schedule.pairs``).  Where they
number at most the configuration's ``check_products``, set-up runs every pair
and then again in turn, ``warmup_products`` or the pairs' count, whichever is
more.  Where they number more, as a code of many workers with several
stragglers has hundreds of masks, set-up runs every B operand unmasked and
then masks drawn from the seed without repeats, ``warmup_products`` in all
(fewer where the masks run out): a window product under a mask set-up never
ran is timed as it comes.  ``CheckSample`` bounds what the check keeps by the
same rule.

This module uses NumPy only; the harness turns what it returns into device
arrays.  Each use draws from its own stream of the seed, so adding a draw to
one never moves another.
"""

from __future__ import annotations

import itertools
import json
import math
import pathlib

import numpy as np

STREAMS = ("tiles", "b", "membership", "check", "warmup")


TRAFFIC_KEYS = {"name", "why", "b_operands", "warmup_products", "membership"}
MEMBERSHIP_KEYS = {"dead_workers"}


def load(path: pathlib.Path) -> dict:
    with open(path) as f:
        traffic = json.load(f)
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
    if int(traffic.get("b_operands", 1)) < 1:
        raise ValueError(f"{path}: b_operands must be >= 1")
    member = traffic.get("membership")
    if member is not None and set(member) != MEMBERSHIP_KEYS:
        raise ValueError(f"{path}: membership needs exactly the keys "
                         f"{sorted(MEMBERSHIP_KEYS)}")
    return traffic


def live_tile_fraction(nnz: int, rows: int, cols: int, block_size: int) -> float:
    """The share of an (rows, cols) matrix's ``block_size``-square tiles that
    hold at least one of ``nnz`` uniformly drawn nonzeros: a tile receives a
    Poisson number of them with mean nnz * block_size**2 / (rows * cols)."""
    return -math.expm1(-nnz * block_size ** 2 / (rows * cols))


def streams(seed: int) -> dict[str, np.random.Generator]:
    """Independent generators for each use of the seed (any non-negative int)."""
    children = np.random.SeedSequence(int(seed)).spawn(len(STREAMS))
    return {name: np.random.default_rng(c) for name, c in zip(STREAMS, children)}


def jax_seed(rng: np.random.Generator) -> int:
    """A seed that ``jax.random.key`` takes with 32-bit integers."""
    return int(rng.integers(0, 2 ** 31 - 1))


def tile_pattern(rng: np.random.Generator, *, row_blocks: int,
                 col_blocks: int, live_fraction: float) -> np.ndarray:
    """(col_blocks, k) sorted row-block indices of A's live tiles."""
    k = max(1, round(live_fraction * row_blocks))
    order = np.argsort(rng.random((col_blocks, row_blocks)), axis=1)
    return np.sort(order[:, :k], axis=1).astype(np.int32)


def tile_values(rng: np.random.Generator, shape) -> np.ndarray:
    """Standard-normal f32 tile values."""
    return rng.standard_normal(shape, dtype=np.float32)


def decodable_masks(coefficients: np.ndarray, dead_workers: int) -> list[np.ndarray]:
    """Every liveness mask with ``dead_workers`` dead whose surviving rows of
    the (N, mn) coefficient matrix still have full column rank."""
    N, d = coefficients.shape
    masks = []
    for dead in itertools.combinations(range(N), dead_workers):
        alive = np.ones(N, bool)
        alive[list(dead)] = False
        if np.linalg.matrix_rank(coefficients * alive[:, None]) >= d:
            masks.append(alive)
    return masks


class Schedule:
    """Which B operand and which liveness mask product ``i`` of the loop uses.

    ``mask(i)`` is None where product ``i`` is decoded from every worker.
    The sequence is a function of the traffic file, the code and the seed
    alone.
    """

    def __init__(self, traffic: dict, rng: np.random.Generator,
                 coefficients: np.ndarray | None = None):
        self.b_operands = int(traffic.get("b_operands", 1))
        member = traffic.get("membership")
        self.dead = 0
        self.masks: list[np.ndarray] = []
        self._decodable: dict[tuple, np.ndarray] = {}
        self._draws: list[np.ndarray | None] = []
        self._rng = rng
        if member:
            if coefficients is None:
                raise ValueError("a membership schedule needs the code's "
                                 "coefficient matrix")
            self.dead = int(member["dead_workers"])
            self.workers = coefficients.shape[0]
            self.masks = decodable_masks(coefficients, self.dead)
            if not self.masks:
                raise ValueError(
                    f"no set of {self.dead} dead workers keeps the code "
                    "decodable")
            self._decodable = {tuple(m): m for m in self.masks}

    def b_index(self, i: int) -> int:
        return i % self.b_operands

    def mask(self, i: int) -> np.ndarray | None:
        """The liveness mask of product ``i``: its stragglers dead where the
        rest decode C, else None (every worker)."""
        if not self.dead:
            return None
        while len(self._draws) <= i:
            alive = np.ones(self.workers, bool)
            alive[self._rng.choice(self.workers, size=self.dead,
                                   replace=False)] = False
            self._draws.append(self._decodable.get(tuple(alive)))
        return self._draws[i]

    @property
    def pairs(self) -> list[tuple[int, np.ndarray | None]]:
        """Every B operand unmasked, then each decodable mask under the first
        operand: the pairs set-up can run."""
        return ([(b, None) for b in range(self.b_operands)]
                + [(0, m) for m in self.masks])

    def warmup(self, products: int, limit: int,
               rng: np.random.Generator) -> list[tuple[int, np.ndarray | None]]:
        """The (B operand, mask) pairs of set-up's products.  Where the pairs
        number at most ``limit``, every pair the loop can use first, then
        again in turn up to ``products``; else every operand unmasked, then
        masks drawn from ``rng`` without repeats up to ``products``."""
        pairs = self.pairs
        if len(pairs) <= limit:
            return [pairs[j % len(pairs)]
                    for j in range(max(products, len(pairs)))]
        drawn = rng.choice(len(self.masks), replace=False,
                           size=min(max(products - self.b_operands, 0),
                                    len(self.masks)))
        return pairs[:self.b_operands] + [(0, self.masks[k]) for k in drawn]


#: the share of the window's products the check keeps beyond each pair's first
SAMPLE_SHARE = 0.2


class CheckSample:
    """Which of the window's products the check keeps, and how much of each.

    A product is kept where its (B operand, mask) pair is new to the window,
    or where fewer than ``products`` are kept with columns and its seeded
    draw falls under ``SAMPLE_SHARE``.  A kept product keeps its checked
    columns and its projection (``"columns"``).  Where set-up's pairs number
    more than ``products``, columns are kept for at most ``products``
    products, and a new pair past them keeps its projection alone
    (``"projection"``): every mask the window uses is still compared, and
    what the check holds on the device stays bounded.
    """

    def __init__(self, products: int, pairs: int):
        self.products = products
        self.bounded = pairs > products
        self.columns = 0
        self._seen: set = set()

    def take(self, pair: tuple, draw: float) -> str | None:
        """What the check keeps of the next product: ``"columns"``,
        ``"projection"`` or None."""
        new = pair not in self._seen
        if not (new or (self.columns < self.products and draw < SAMPLE_SHARE)):
            return None
        self._seen.add(pair)
        if self.bounded and self.columns >= self.products:
            return "projection"
        self.columns += 1
        return "columns"
