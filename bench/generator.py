"""What every traffic process shares.  A mix is a JSON file
``bench/traffic/<mix>.json`` of parameters; its ``"process"`` names the
module ``bench/traffic/<process>.py`` that turns them into traffic
(``spec.process`` finds it by that name).  A process module has

* ``ENTRY``: the program entry it drives, ``"serve"`` (``RecsysEngine``)
  or ``"train"`` (the ``Trainer`` step);
* for ``"serve"``: ``requests(mix, model, seed, stream, seconds, rate=None)
  -> Requests``, the requests of a span from stream ``stream`` of
  ``seed``, and ``Loop``, the client that drives the engine with them
  (``bench.serve.OpenLoop`` for an open loop; a closed loop brings its own
  class with the same attributes);
* for ``"train"``: ``batch_fn(mix, model, seed)``, a jitted ``step ->
  {dense, sparse, label}`` (``sparse`` in a form ``reference.api.
  train_steps`` takes, with its ``mask`` where it has one).

Adding a process is adding its module; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

# independent random streams of one seed
WINDOW, WARM_BUCKETS, WARM, TRACED = 0, 1, 2, 3


@dataclasses.dataclass
class Requests:
    """``n`` requests of one span: ``due`` seconds from the span's start,
    ``dense (n, dense_dim)`` and the ids of every bag, flat, in feature
    order (``offsets`` split one request's ids into its bags)."""
    due: np.ndarray
    dense: np.ndarray
    ids: np.ndarray
    offsets: np.ndarray

    def __len__(self) -> int:
        return len(self.due)

    def bags(self, i: int) -> list[np.ndarray]:
        return np.split(self.ids[i], self.offsets)

    def padded(self, rows: np.ndarray):
        """``(dense, idx, mask)`` of requests ``rows``, bags padded to the
        longest with masked slots."""
        lens = np.diff(np.concatenate([[0], self.offsets, [self.ids.shape[1]]]))
        f, lmax = len(lens), int(lens.max())
        idx = np.zeros((len(rows), f, lmax), np.int32)
        mask = np.zeros((len(rows), f, lmax), np.float32)
        start = 0
        for j, n in enumerate(lens):
            idx[:, j, :n] = self.ids[rows, start:start + n]
            mask[:, j, :n] = 1.0
            start += n
        return self.dense[rows], idx, mask


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def bag_lengths(mix: dict, model: dict) -> np.ndarray:
    lens = mix.get("bag_lengths") or [1] * len(model["table_sizes"])
    if len(lens) != len(model["table_sizes"]) or min(lens) < 1:
        raise ValueError("bag_lengths needs one length >= 1 per feature")
    return np.asarray(lens, np.int64)


def draw_requests(mix: dict, model: dict, rng: np.random.Generator,
                  due: np.ndarray) -> Requests:
    """Requests due at ``due``: ``dense_dim`` features from N(0, 1) and, for
    feature ``f``, a bag of ``bag_lengths[f]`` ids (one id where the mix
    gives none), each ``floor(u**skew * S_f)`` with ``u`` uniform: low ids
    are hot, as in ``launch/serve.py``'s generator, vectorised."""
    n = len(due)
    lens = bag_lengths(mix, model)
    sizes = np.repeat(np.asarray(model["table_sizes"], np.int64), lens)
    dense = rng.standard_normal((n, model["dense_dim"]), np.float32)
    u = rng.random((n, int(lens.sum())))
    ids = np.minimum(np.floor(u ** mix["skew"] * sizes).astype(np.int64),
                     sizes - 1)
    return Requests(due, dense, ids, np.cumsum(lens)[:-1])


def planted(seed: int, tag: str, shape):
    """Weights ``N(0, 1/shape[0])`` of the planted logistic model that
    labels training batches, drawn from ``seed`` and ``tag``."""
    import jax
    key = jax.random.PRNGKey(zlib.crc32(f"{seed}:{tag}".encode()) % (2 ** 31))
    return jax.random.normal(key, shape) / np.sqrt(shape[0])
