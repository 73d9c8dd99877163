"""FL client trainers.

A *Trainer* binds a model family to the FL loop:
    init_params(seed)                          -> params
    local_train(params, client_id, rnd_seed)   -> (new_params, n_samples)
    evaluate(params)                           -> accuracy in [0,1]

``CNNTrainer`` reproduces the paper's workloads (CNN / ResNet8, real SGD
on real batches).  ``LMTrainer`` makes any assigned LLM architecture an
FL workload (reduced config on CPU; full config under pjit on a mesh) —
its "accuracy" is next-token top-1 on a held-out batch, which drives
Eq. 3 tier movement exactly like test accuracy does for CNNs.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import FLConfig, ModelConfig
from repro.data.partition import primary_class_partition
from repro.data.pipeline import (ClientDataset, client_batches,
                                 epoch_batch_rows)
from repro.data.synthetic import make_image_dataset, make_token_dataset
from repro.models.cnn import cnn_forward, cnn_loss, init_cnn
from repro.models.transformer import forward as lm_forward
from repro.models.transformer import init_model, lm_loss
from repro.obs import telemetry as obs
from repro.optim import make_optimizer


class CNNTrainer:
    def __init__(self, cfg: ModelConfig, fl: FLConfig, dataset: str,
                 scale: float = 0.05):
        self.cfg = cfg
        self.fl = fl
        data = make_image_dataset(dataset, seed=fl.seed, scale=scale)
        parts = primary_class_partition(
            data["y_train"], fl.n_clients, fl.primary_frac, seed=fl.seed)
        self.clients: List[ClientDataset] = [
            ClientDataset(data["x_train"][p], data["y_train"][p])
            for p in parts]
        # The training split stays on the device as a sample table, one
        # flat row per sample and the labels beside it; a round sends
        # only table rows (``parts`` maps a client's samples to rows).
        x_train = data["x_train"]
        self._parts = parts
        self._sample_shape = x_train.shape[1:]
        table = (jnp.asarray(x_train.reshape(len(x_train), -1)),
                 jnp.asarray(data["y_train"]))
        # by placement: None is the default device; a client-sharded
        # runner's replicated sharding gets a copy of its own
        self._tables: Dict[object, tuple] = {None: table}
        obs.TEL.gauge("train.resident_bytes",
                      table[0].nbytes + table[1].nbytes)
        self.x_test = jnp.asarray(data["x_test"])
        self.y_test = jnp.asarray(data["y_test"])
        self.opt = make_optimizer(fl.optimizer)
        self._step = jax.jit(self._step_impl, static_argnames=("im2col",))
        self._eval = jax.jit(self._eval_impl)
        self._gather = jax.jit(self._gather_batches)
        self._batch_train = jax.jit(self._batch_train_impl)
        self._batch_train_multi = jax.jit(self._batch_train_multi_impl)

    def _step_impl(self, params, opt_state, x, y, im2col: bool = False):
        loss, grads = jax.value_and_grad(
            lambda p: cnn_loss(self.cfg, p, {"x": x, "y": y},
                               im2col=im2col))(params)
        ups, opt_state = self.opt.update(grads, opt_state, params, self.fl.lr)
        params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
            params, ups)
        return params, opt_state, loss

    def _eval_impl(self, params, x, y):
        logits = cnn_forward(self.cfg, params, x)
        return jnp.mean(jnp.argmax(logits, -1) == y)

    def init_params(self, seed: int = 0):
        return init_cnn(self.cfg, jax.random.PRNGKey(seed))

    def local_train(self, params, client_id: int, rnd_seed: int):
        ds = self.clients[client_id]
        opt_state = self.opt.init(params)
        for ep in range(self.fl.local_epochs):
            for x, y in client_batches(ds, self.fl.batch_size,
                                       rnd_seed * 131 + ep):
                params, opt_state, _ = self._step(
                    params, opt_state, jnp.asarray(x), jnp.asarray(y))
        return params, len(ds)

    # -- batched multi-client path (engine hot path) --------------------
    def _client_epoch_rows(self, client_id: int, rnd_seed: int):
        """Sample-table rows of all local-training batches for one
        client, (T, B) int32: the stream of the looped ``local_train``
        (same seeds, same order, same dropped tail)."""
        part = self._parts[client_id]
        return np.concatenate([
            part[epoch_batch_rows(len(part), self.fl.batch_size,
                                  rnd_seed * 131 + ep)]
            for ep in range(self.fl.local_epochs)]).astype(np.int32)

    def _gather_batches(self, table_x, table_y, rows):
        """rows (C, T, B) -> the batches xs (C, T, B, H, W, ch), ys.

        A program of its own, ahead of the training program: the
        training program then compiles as it does for batches copied
        from the host, and trains bit-identically to it (fused into it,
        the gather changed the 16-row program's rounding on a TPU v5e).
        """
        xs = table_x[rows].reshape(rows.shape + self._sample_shape)
        return xs, table_y[rows]

    def _resident_tables(self, run):
        """The sample table as ``run`` reads it: the copy made at init,
        or one replicated over the mesh of a client-sharded runner
        (its ``replicated_sharding``), made on first use."""
        sharding = getattr(run, "replicated_sharding", None)
        if sharding not in self._tables:
            self._tables[sharding] = jax.device_put(self._tables[None],
                                                    sharding)
        return self._tables[sharding]

    def _batch_train_impl(self, params, xs, ys):
        """xs (C, T, B, H, W, ch), ys (C, T, B) -> stacked params (C, ...).

        vmap over the client axis of a lax.scan over local steps: the
        whole multi-client round is ONE compiled XLA program instead of
        C * T eager dispatches.
        """
        def one_client(x_seq, y_seq):
            opt_state = self.opt.init(params)
            def step(carry, xy):
                p, o = carry
                # im2col keeps per-client conv kernels on the GEMM fast
                # path under the client-axis vmap
                p, o, loss = self._step_impl(p, o, xy[0], xy[1],
                                             im2col=True)
                return (p, o), loss
            (p, _), _ = jax.lax.scan(step, (params, opt_state),
                                     (x_seq, y_seq))
            return p
        return jax.vmap(one_client)(xs, ys)

    def _bucketed_train(self, keys, train_chunk, wrap):
        """Shared shape-bucketing for the batched paths: build each
        (client, seed)-keyed stream of table rows once, bucket positions
        by stream shape (ragged partitions), gather each bucket's
        batches on the device, run ``train_chunk(xs, ys, positions)``
        per bucket, and reassemble chunk rows in input order.  ``wrap``
        (see ``local_train_batch``) runs the gather too, with the two
        sample tables replicated.  Spans: ``train.batches`` (row streams
        and stacks, on the host), ``train.h2d`` (the rows' copy to the
        device), ``train.dispatch`` (the gather and bucket programs)."""
        gather = (self._gather if wrap is None
                  else wrap(self._gather_batches, 2))
        table_x, table_y = self._resident_tables(gather)
        tel = obs.TEL
        with tel.span("train.batches") as span:
            data = {}                 # pad slots repeat (client, seed)
            buckets: Dict[tuple, List[int]] = {}
            for pos, key in enumerate(keys):
                if key not in data:   # keys, so compute each stream once
                    data[key] = self._client_epoch_rows(*key)
                buckets.setdefault(data[key].shape, []).append(pos)
            host = [np.stack([data[keys[p]] for p in positions])
                    for positions in buckets.values()]
            if tel.enabled:
                span.set(streams=len(data))
        nbytes = sum(rows.nbytes for rows in host)
        with tel.span("train.h2d", bytes=nbytes):
            dev = [jnp.asarray(rows) for rows in host]
        with tel.span("train.dispatch"):
            chunks = [train_chunk(*gather(table_x, table_y, rows), positions)
                      for rows, positions in zip(dev, buckets.values())]
        if len(chunks) == 1:          # common case: one shape bucket,
            return chunks[0]          # order already the input order
        order = [p for positions in buckets.values() for p in positions]
        inv = np.argsort(np.asarray(order))
        return jax.tree_util.tree_map(
            lambda *leaves: jnp.concatenate(leaves, axis=0)[inv], *chunks)

    def local_train_batch(self, params, client_ids, rnd_seed: int, *,
                          wrap=None):
        """Train many clients in one jitted vmapped scan.

        Clients whose local batch streams have differing shapes (ragged
        partitions) are bucketed by shape; each bucket is one compiled
        call.  Returns (stacked_params with leading axis len(client_ids)
        in input order, sizes array).

        ``wrap`` is the distributed-engine hook: it receives the pure
        train function plus the number of leading replicated args and
        returns the runner to use (the client-sharded shard_map path).
        """
        sizes = np.asarray([len(self.clients[c]) for c in client_ids],
                           np.float32)
        run = (self._batch_train if wrap is None
               else wrap(self._batch_train_impl, 1))
        stacked = self._bucketed_train(
            [(c, rnd_seed) for c in client_ids],
            lambda xs, ys, positions: run(params, xs, ys), wrap)
        return stacked, sizes

    # -- per-client start params (async runtime hot path) ---------------
    def _batch_train_multi_impl(self, start_params, xs, ys):
        """Like ``_batch_train_impl`` but every client starts from its
        OWN model snapshot: ``start_params`` carries a leading client
        axis, vmapped alongside the data."""
        def one_client(p0, x_seq, y_seq):
            opt_state = self.opt.init(p0)
            def step(carry, xy):
                p, o = carry
                p, o, loss = self._step_impl(p, o, xy[0], xy[1],
                                             im2col=True)
                return (p, o), loss
            (p, _), _ = jax.lax.scan(step, (p0, opt_state), (x_seq, y_seq))
            return p
        return jax.vmap(one_client)(start_params, xs, ys)

    def local_train_cohort(self, start_params, client_ids, rnd_seeds, *,
                           wrap=None):
        """Async-window cohort: per-client start models AND per-client
        data-stream seeds, one jitted vmapped scan.

        ``start_params`` is a stacked pytree (leading axis
        len(client_ids)) of the model snapshot each client trains from;
        batch streams are identical to looping
        ``local_train(start_i, c_i, seed_i)``.  ``wrap``: see
        ``local_train_batch`` (every arg is per-client here, so zero
        replicated args).
        """
        sizes = np.asarray([len(self.clients[c]) for c in client_ids],
                           np.float32)
        run = (self._batch_train_multi if wrap is None
               else wrap(self._batch_train_multi_impl, 0))

        def chunk(xs, ys, positions):
            idx = jnp.asarray(np.asarray(positions, np.int32))
            starts = jax.tree_util.tree_map(lambda l: l[idx], start_params)
            return run(starts, xs, ys)

        stacked = self._bucketed_train(list(zip(client_ids, rnd_seeds)),
                                       chunk, wrap)
        return stacked, sizes

    def evaluate(self, params, max_samples: int = 2048) -> float:
        n = min(max_samples, self.x_test.shape[0])
        accs = []
        for i in range(0, n, 512):
            accs.append(float(self._eval(params, self.x_test[i:i + 512],
                                         self.y_test[i:i + 512])))
        return float(np.mean(accs))


class LMTrainer:
    """FL over a (reduced or pjit-sharded) LM architecture."""

    def __init__(self, cfg: ModelConfig, fl: FLConfig, seq_len: int = 128,
                 batch: int = 8, corpus_tokens: int = 200_000,
                 step_fn=None, init_fn=None):
        self.cfg = cfg
        self.fl = fl
        self.seq = seq_len
        self.batch = batch
        toks = make_token_dataset(cfg.vocab_size, corpus_tokens, seed=fl.seed)
        splits = np.array_split(toks[:-corpus_tokens // 10], fl.n_clients)
        self.client_toks = splits
        self.test_toks = toks[-corpus_tokens // 10:]
        self.opt = make_optimizer(fl.optimizer)
        self._custom_step = step_fn is not None
        self._step = step_fn or jax.jit(self._step_impl)
        self._init_fn = init_fn
        self._eval = jax.jit(self._eval_impl)
        self._batch_train = jax.jit(self._batch_train_impl)
        self._batch_train_multi = jax.jit(self._batch_train_multi_impl)

    def _step_impl(self, params, opt_state, tokens):
        def loss_fn(p):
            l, _ = lm_loss(self.cfg, p, {"tokens": tokens})
            return l
        loss, grads = jax.value_and_grad(loss_fn)(params)
        ups, opt_state = self.opt.update(grads, opt_state, params, self.fl.lr)
        params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)
                          ).astype(p.dtype), params, ups)
        return params, opt_state, loss

    def _eval_impl(self, params, tokens):
        logits, _ = lm_forward(self.cfg, params, {"tokens": tokens})
        pred = jnp.argmax(logits[:, :-1], -1)
        return jnp.mean(pred == tokens[:, 1:])

    def _batch(self, toks: np.ndarray, seed: int) -> np.ndarray:
        rng = np.random.default_rng(seed)
        n = max(len(toks) - self.seq - 1, 1)
        starts = rng.integers(0, n, self.batch)
        return np.stack([toks[s:s + self.seq] for s in starts])

    def init_params(self, seed: int = 0):
        if self._init_fn is not None:
            return self._init_fn(seed)
        return init_model(self.cfg, jax.random.PRNGKey(seed))

    def local_train(self, params, client_id: int, rnd_seed: int):
        toks = self.client_toks[client_id]
        opt_state = self.opt.init(params)
        for ep in range(self.fl.local_epochs):
            b = jnp.asarray(self._batch(toks, rnd_seed * 131 + ep))
            params, opt_state, _ = self._step(params, opt_state, b)
        return params, len(toks)

    def _batch_train_impl(self, params, tokens):
        """tokens (C, E, B, S) -> stacked params (C, ...)."""
        def one_client(tok_seq):
            opt_state = self.opt.init(params)
            def step(carry, tok):
                p, o = carry
                p, o, loss = self._step_impl(p, o, tok)
                return (p, o), loss
            (p, _), _ = jax.lax.scan(step, (params, opt_state), tok_seq)
            return p
        return jax.vmap(one_client)(tokens)

    def local_train_batch(self, params, client_ids, rnd_seed: int, *,
                          wrap=None):
        """One jitted vmapped scan over all clients' local epochs; batch
        streams are identical to the looped ``local_train``.  ``wrap``
        is the distributed-engine hook (see ``CNNTrainer``)."""
        if self._custom_step:
            raise NotImplementedError(
                "custom step_fn (pjit) trainers use the looped path")
        toks = np.stack([
            np.stack([self._batch(self.client_toks[c], rnd_seed * 131 + ep)
                      for ep in range(self.fl.local_epochs)])
            for c in client_ids])                   # (C, E, B, S)
        run = (self._batch_train if wrap is None
               else wrap(self._batch_train_impl, 1))
        stacked = run(params, jnp.asarray(toks))
        sizes = np.asarray([len(self.client_toks[c]) for c in client_ids],
                           np.float32)
        return stacked, sizes

    def _batch_train_multi_impl(self, start_params, tokens):
        """tokens (C, E, B, S), start_params stacked (C, ...): every
        client trains from its own snapshot."""
        def one_client(p0, tok_seq):
            opt_state = self.opt.init(p0)
            def step(carry, tok):
                p, o = carry
                p, o, loss = self._step_impl(p, o, tok)
                return (p, o), loss
            (p, _), _ = jax.lax.scan(step, (p0, opt_state), tok_seq)
            return p
        return jax.vmap(one_client)(start_params, tokens)

    def local_train_cohort(self, start_params, client_ids, rnd_seeds, *,
                           wrap=None):
        """Async-window cohort: per-client start models and per-client
        seeds; batch streams identical to looping
        ``local_train(start_i, c_i, seed_i)``."""
        if self._custom_step:
            raise NotImplementedError(
                "custom step_fn (pjit) trainers use the looped path")
        toks = np.stack([
            np.stack([self._batch(self.client_toks[c], s * 131 + ep)
                      for ep in range(self.fl.local_epochs)])
            for c, s in zip(client_ids, rnd_seeds)])    # (C, E, B, S)
        run = (self._batch_train_multi if wrap is None
               else wrap(self._batch_train_multi_impl, 0))
        stacked = run(start_params, jnp.asarray(toks))
        sizes = np.asarray([len(self.client_toks[c]) for c in client_ids],
                           np.float32)
        return stacked, sizes

    def evaluate(self, params) -> float:
        b = jnp.asarray(self._batch(self.test_toks, 1234))
        return float(self._eval(params, b))


def build_fl_clients(arch_id: str, fl: FLConfig, dataset: Optional[str] = None,
                     scale: float = 0.05, reduced: bool = True):
    """Factory: any registered arch becomes an FL workload."""
    from repro.config import get_arch
    cfg = get_arch(arch_id)
    if cfg.family == "cnn":
        ds = dataset or {"cnn-mnist": "mnist", "cnn-fmnist": "fmnist",
                         "resnet8-cifar10": "cifar10"}[arch_id]
        return CNNTrainer(cfg, fl, ds, scale=scale)
    if reduced:
        cfg = cfg.reduced()
    return LMTrainer(cfg, fl)
