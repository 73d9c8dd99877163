"""The CNN trainer keeps the training split on the device as a sample
table: a round sends only each client's batch rows, and the batches are
gathered on the device ahead of the cohort program.  The gathered
batches are the ``client_batches`` stream exactly, and the trained rows
equal those of the host-stream path the table replaced (numpy batches
from ``client_batches``, stacked per shape bucket and copied), for the
sync and the per-client-start cohort paths, through a cohort padded to
a pow2 bucket and a ragged partition that splits into shape buckets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl.client
from repro.config import get_arch
from repro.config.base import FLConfig
from repro.core.engine import BatchedClientEngine
from repro.data.pipeline import client_batches
from repro.fl.client import CNNTrainer

FL = FLConfig(n_clients=6, n_tiers=2, tau=2, rounds=1, seed=0,
              primary_frac=0.7)
COHORT = [0, 1, 2, 3, 4]            # padded to 8 rows by the engine
_partition = repro.fl.client.primary_class_partition


def _ragged(*args, **kw):
    """Client 1 keeps half its samples (a shorter stream: a second shape
    bucket); client 5 keeps fewer than one batch (one short batch)."""
    parts = _partition(*args, **kw)
    parts[1] = parts[1][:len(parts[1]) // 2]
    parts[5] = parts[5][:7]
    return parts


@pytest.fixture(scope="module", params=["even", "ragged"])
def trainer(request):
    with pytest.MonkeyPatch.context() as mp:
        if request.param == "ragged":
            mp.setattr(repro.fl.client, "primary_class_partition", _ragged)
        return CNNTrainer(get_arch("cnn-mnist").reduced(), FL, "mnist",
                          scale=0.01)


def _stream(tr, client, seed):
    """One client's whole host batch stream, as ``local_train`` sees it."""
    xs, ys = zip(*[b for ep in range(FL.local_epochs)
                   for b in client_batches(tr.clients[client],
                                           FL.batch_size, seed * 131 + ep)])
    return np.stack(xs), np.stack(ys)


def _host_rows(tr, keys, train):
    """The host-stream path: a numpy stream per (client, seed) key,
    stacked per shape bucket, ``train(xs, ys, positions)`` per bucket,
    rows put back in key order."""
    streams = [_stream(tr, *k) for k in keys]
    buckets = {}
    for pos, (xs, _) in enumerate(streams):
        buckets.setdefault(xs.shape, []).append(pos)
    rows = [None] * len(keys)
    for positions in buckets.values():
        xs, ys = (jnp.asarray(np.stack([streams[p][k] for p in positions]))
                  for k in (0, 1))
        out = train(xs, ys, positions)
        for i, p in enumerate(positions):
            rows[p] = jax.tree_util.tree_map(lambda l, i=i: l[i], out)
    return jax.tree_util.tree_map(lambda *r: jnp.stack(r), *rows)


def _assert_rows_equal(got, want, n):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape[0] == n
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b[:n]))


def _pad(keys):
    return keys + [keys[-1]] * ((1 << (len(keys) - 1).bit_length())
                                - len(keys))


def test_gathered_batches_are_the_client_batches_stream(trainer):
    table_x, table_y = trainer._resident_tables(None)
    shapes = set()
    for c in range(FL.n_clients):
        for seed in (0, 2 ** 31 + 5):
            rows = trainer._client_epoch_rows(c, seed)
            assert rows.dtype == np.int32
            xs, ys = trainer._gather(table_x, table_y,
                                     jnp.asarray(rows[None]))
            want_x, want_y = _stream(trainer, c, seed)
            np.testing.assert_array_equal(np.asarray(xs[0]), want_x)
            np.testing.assert_array_equal(np.asarray(ys[0]), want_y)
            shapes.add(rows.shape)
    assert len(shapes) == (1 if len(set(map(len, trainer.clients))) == 1
                           else 3)


def test_sync_rows_match_the_host_stream_path(trainer):
    params = trainer.init_params(0)
    stacked, sizes = BatchedClientEngine(trainer).train_clients(
        params, COHORT, rnd_seed=3)
    train = jax.jit(trainer._batch_train_impl)
    want = _host_rows(trainer, _pad([(c, 3) for c in COHORT]),
                      lambda xs, ys, positions: train(params, xs, ys))
    _assert_rows_equal(stacked, want, len(COHORT))
    np.testing.assert_array_equal(
        sizes, [len(trainer.clients[c]) for c in COHORT])


def test_cohort_rows_match_the_host_stream_path(trainer):
    seeds = [11 * c + 2 for c in COHORT]
    starts = [trainer.init_params(c % 2) for c in COHORT]
    stacked, _ = BatchedClientEngine(trainer).train_cohort(starts, COHORT,
                                                           seeds)
    train = jax.jit(trainer._batch_train_multi_impl)
    padded = _pad(list(range(len(COHORT))))
    stacked_starts = jax.tree_util.tree_map(
        lambda *l: jnp.stack(l), *[starts[i] for i in padded])

    def chunk(xs, ys, positions):
        idx = jnp.asarray(positions)
        return train(jax.tree_util.tree_map(lambda l: l[idx],
                                            stacked_starts), xs, ys)

    want = _host_rows(trainer, [(COHORT[i], seeds[i]) for i in padded],
                      chunk)
    _assert_rows_equal(stacked, want, len(COHORT))
