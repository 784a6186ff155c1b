"""The one traffic generator: reads a mix from traffic/<mix>.json and
draws windows of operations from the run's seed.

Every seed gives the same sequence of operation kinds and sizes over
the same key space; the keys and the values differ.
"""

from __future__ import annotations

import collections
import importlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)
LAYOUT_SEED = 24        # of the stream that draws kinds and sizes
CHUNK_WINDOWS = 128     # windows drawn at a time


def load_json(kind: str, name: str) -> dict:
    """benchmarks/<kind>/<name>.json, found by name."""
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def op_module(kind: str):
    """benchmarks/ops/<kind>.py, found by name."""
    return importlib.import_module(f"benchmarks.ops.{kind}")


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """YCSB's FNV-1a over the 8 bytes of each value (Utils.fnvhash64)."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (x & np.uint64(0xFF))) * _FNV_PRIME
            x = x >> np.uint64(8)
    return h


class Zipfian:
    """YCSB's ZipfianGenerator (Gray et al.'s method) over n items."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = n, theta
        self.zetan = float(np.sum(1.0 / np.arange(1, n + 1) ** theta))
        zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1 - (2.0 / n) ** (1 - theta)) / (1 - zeta2 / self.zetan))

    def draw(self, rng, size: int) -> np.ndarray:
        u = rng.random(size)
        uz = u * self.zetan
        out = (self.n * (self.eta * u - self.eta + 1) ** self.alpha)
        out = np.minimum(out.astype(np.int64), self.n - 1)
        out[uz < 1.0 + 0.5 ** self.theta] = 1
        out[uz < 1.0] = 0
        return out


def key_drawer(spec: dict, n_items: int):
    """A function (rng, size) -> item numbers in [0, n_items) for a
    `key` entry of a traffic file."""
    dist = spec["dist"]
    if dist == "uniform":
        return lambda rng, size: rng.integers(0, n_items, size=size)
    if dist == "scrambled_zipfian":
        z = Zipfian(n_items, spec["theta"])
        return lambda rng, size: (
            fnv1a64(z.draw(rng, size)) % np.uint64(n_items)).astype(np.int64)
    raise ValueError(f"unknown key distribution {dist!r}")


class Schedule:
    """Windows of operations for one run.

    Each operation's kind is drawn by its share and each size a kind
    asks for (a scan's length) from its distribution, one operation at
    a time as YCSB's CoreWorkload does, from a stream of their own that
    starts at LAYOUT_SEED: every seed sends the same sequence of kinds
    and sizes. The run's seed draws the keys and the values.
    `window_ops` consecutive operations make a window. Windows are drawn
    CHUNK_WINDOWS at a time: `draw` during set-up, for as many as the
    run is expected to send; a run that outruns them draws the next
    chunk inside the window."""

    def __init__(self, traffic: dict, ctx: dict, seed: int):
        self.traffic, self.ctx = traffic, ctx
        self.rng = np.random.default_rng([seed, 0x7aff1c])
        self.shape_rng = np.random.default_rng(LAYOUT_SEED)
        self.mods = [op_module(o["kind"]) for o in traffic["ops"]]
        shares = np.array([o["share"] for o in traffic["ops"]], float)
        self.shares = shares / shares.sum()
        self._windows = collections.deque()
        self.late_chunks = 0    # drawn by next_window, so inside a window

    def draw(self, n_windows: int) -> None:
        """Draw until n_windows are waiting."""
        while len(self._windows) < n_windows:
            self._fill()

    def _fill(self) -> None:
        w_ops = self.traffic["window_ops"]
        kinds = self.shape_rng.choice(len(self.mods),
                                      size=CHUNK_WINDOWS * w_ops,
                                      p=self.shares)
        drawn = [iter(mod.draw(self.rng, self.shape_rng,
                               int((kinds == k).sum()),
                               self.traffic["ops"][k], self.ctx))
                 for k, mod in enumerate(self.mods)]
        for row in kinds.reshape(CHUNK_WINDOWS, w_ops).tolist():
            window = [[] for _ in self.mods]
            for k in row:
                window[k].append(next(drawn[k]))
            self._windows.append(window)

    def next_window(self):
        """[[args of kind 0], [args of kind 1], ...] in the mix's order."""
        if not self._windows:
            self.late_chunks += 1
            self._fill()
        return self._windows.popleft()
