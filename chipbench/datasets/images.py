"""Image data (``"data": "images"``): the benchmark's own copy of the
clients' data, the synthetic image sets of the paper's shapes and the
primary-class partition (FedDCT §5.1, share "#" of each client's
samples from one class).

The reference trains on these samples, never on the program's, and
the check counts the clients whose samples the program's trainer holds
otherwise (``data_gap``), so a change to the data or the partition
cannot quietly change the work or be seen alike by both sides.  The
draws follow the program's generator stream for stream; the per-sample
shift is one gather here instead of a Python loop of ``np.roll``.
"""

from __future__ import annotations

import functools
import zlib
from typing import List, Tuple

import numpy as np

SPECS = {"mnist": dict(hw=(28, 28, 1), n_classes=10, n_train=60_000)}


def _prototypes(rng, hw, n_classes, n_gratings=6):
    h, w, c = hw
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    protos = np.zeros((n_classes, h, w, c), np.float32)
    for k in range(n_classes):
        for _ in range(n_gratings):
            fx, fy = rng.uniform(0.05, 0.5, 2)
            ph = rng.uniform(0, 2 * np.pi)
            amp = rng.uniform(0.4, 1.0)
            cx, cy = rng.uniform(0.2, 0.8, 2) * np.array([w, h])
            env = np.exp(-(((xx - cx) / (0.4 * w)) ** 2
                           + ((yy - cy) / (0.4 * h)) ** 2))
            g = amp * env * np.sin(2 * np.pi * (fx * xx + fy * yy) + ph)
            for ch in range(c):
                protos[k, :, :, ch] += g * rng.uniform(0.5, 1.0)
    protos /= np.abs(protos).max(axis=(1, 2, 3), keepdims=True) + 1e-6
    return protos


def train_set(name: str, seed: int, scale: float
              ) -> Tuple[np.ndarray, np.ndarray]:
    """The training samples (x (N, H, W, C) f32, y (N,) int32)."""
    spec = SPECS[name]
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % 2 ** 16)
    (h, w, _), ncls = spec["hw"], spec["n_classes"]
    n = int(spec["n_train"] * scale)
    protos = _prototypes(rng, spec["hw"], ncls)
    y = rng.integers(0, ncls, n).astype(np.int32)
    sx = rng.integers(-2, 3, n)
    sy = rng.integers(-2, 3, n)
    rows = (np.arange(h)[None, :] - sx[:, None]) % h          # (n, h)
    cols = (np.arange(w)[None, :] - sy[:, None]) % w          # (n, w)
    x = protos[y[:, None, None], rows[:, :, None], cols[:, None, :]]
    x = x * rng.uniform(0.7, 1.3, (n, 1, 1, 1)).astype(np.float32)
    x = x + rng.normal(0, 0.35, x.shape).astype(np.float32)
    return x.astype(np.float32), y


def partition(labels: np.ndarray, n_clients: int, primary_frac: float,
              seed: int) -> List[np.ndarray]:
    """Each client draws a primary class for ``primary_frac`` of its
    samples and fills the rest from the largest other classes."""
    n_classes = int(labels.max()) + 1
    rng = np.random.default_rng(seed)
    if primary_frac <= 1.0 / n_classes:
        idx = rng.permutation(len(labels))
        return [np.sort(s) for s in np.array_split(idx, n_clients)]
    by_class = [rng.permutation(np.where(labels == c)[0]).tolist()
                for c in range(n_classes)]
    per_client = len(labels) // n_clients
    n_primary = int(round(primary_frac * per_client))
    primaries = rng.integers(0, n_classes, n_clients)
    out = []
    for ci in range(n_clients):
        pc = int(primaries[ci])
        take = by_class[pc][:n_primary]
        by_class[pc] = by_class[pc][len(take):]
        others = [c for c in range(n_classes) if c != pc]
        for _ in range(per_client - len(take)):
            sizes = [len(by_class[c]) for c in others]
            if not any(sizes):
                break
            take.append(by_class[others[int(np.argmax(sizes))]].pop())
        out.append(np.array(sorted(take), np.int64))
    return out


def clients(cfg: dict) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Every client's (x, y) as the configuration states them."""
    fed = cfg["federation"]
    return _clients(cfg["dataset"], cfg["federation_seed"], cfg["data_scale"],
                    fed["n_clients"], fed["primary_frac"])


@functools.lru_cache(maxsize=1)
def _clients(name, seed, scale, n_clients, primary_frac):
    x, y = train_set(name, seed, scale)
    return [(x[p], y[p]) for p in partition(y, n_clients, primary_frac, seed)]


def data_gap(trainer, ours) -> int:
    """Clients whose samples the trainer (its ``clients``, each with
    ``.x`` and ``.y``) holds otherwise than ``ours``."""
    theirs = trainer.clients
    bad = len(theirs) != len(ours)
    for t, (x, y) in zip(theirs, ours):
        bad += not (np.array_equal(np.asarray(t.x), x)
                    and np.array_equal(np.asarray(t.y), y))
    return int(bad)


def build_trainer(cfg: dict, fl):
    """The program's image trainer over the configuration's data set."""
    from repro.fl.client import build_fl_clients
    return build_fl_clients(cfg["arch"], fl, dataset=cfg["dataset"],
                            scale=cfg["data_scale"])


def n_samples(client) -> int:
    return len(client[1])


def client_stream(cfg: dict, client, seed: int):
    """A client's local batches for one round: per epoch a permutation
    drawn from ``seed * 131 + epoch``, cut into full batches (the ragged
    tail is dropped).  -> xs (T, B, ...), ys (T, B)."""
    x, y = client
    fed = cfg["federation"]
    batch = fed["batch_size"]
    xs, ys = [], []
    for ep in range(fed["local_epochs"]):
        idx = np.random.default_rng(seed * 131 + ep).permutation(len(y))
        for b in range(max(len(y) // batch, 1)):
            sl = idx[b * batch:(b + 1) * batch]
            xs.append(x[sl])
            ys.append(y[sl])
    return np.stack(xs), np.stack(ys)


def check(cfg: dict, trainer):
    """What in the trainer's clients, sample counts and local steps
    differs from the configuration, or ``None``."""
    n = cfg["federation"]["n_clients"]
    sizes = sorted({len(c) for c in trainer.clients})
    if len(trainer.clients) != n or sizes != [cfg["samples_per_client"]]:
        return (f"the trainer built {len(trainer.clients)} clients with "
                f"sample counts {sizes[:8]}; the configuration states {n} "
                f"x {cfg['samples_per_client']}")
    steps = (cfg["samples_per_client"] // cfg["federation"]["batch_size"]
             * cfg["federation"]["local_epochs"])
    if steps != cfg["local_steps"]:
        return (f"{steps} local steps, the configuration states "
                f"{cfg['local_steps']}")
    return None
