"""Device-resident flat client-state store (``ClientStateStore``):
exact mixed-dtype gather/scatter round-trips, donation safety under
repeated in-place updates, the fused merge+scatter program, the
device-side all-masked round guard, and — the acceptance gate —
bit-identical ``RunHistory`` store vs dict-of-pytrees paths for
``fedasync(window=0/K)``, ``fedbuff`` and ``feddct_async``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import FLConfig
from repro.core.aggregation import (aggregate_or_keep,
                                    staleness_merge_coefficients,
                                    staleness_weighted_merge)
from repro.core.baselines import run_fedasync, run_fedbuff
from repro.core.engine import make_engine
from repro.core.state import ClientStateStore
from repro.fl.network import WirelessNetwork
from repro.fl.testing import SyntheticCohortTrainer
from repro.kernels.ops import quantize_rows
from repro.kernels.ref import dequantize_rows_ref, quantize_rows_ref
from repro.runtime.async_loop import run_feddct_async


def _template(seed=0):
    """Mixed-dtype model pytree: 2-d f32, bf16 vector, f16 vector,
    scalar — every leaf dtype round-trips exactly through f32 rows."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(5,)).astype(np.float32)
                         ).astype(jnp.bfloat16),
        "h": jnp.asarray(rng.normal(size=(3,)).astype(np.float32)
                         ).astype(jnp.float16),
        "s": jnp.float32(rng.normal()),
    }


def _tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        if jnp.issubdtype(x.dtype, jnp.floating):
            # f32 view: bf16/f16 compare exactly through it
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))
        else:
            # int/bool leaves compare exactly in their own dtype (an
            # f32 view would hide precision loss above 2^24)
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# flat row <-> pytree round-trips
# ---------------------------------------------------------------------------

def test_flatten_unflatten_roundtrip_exact_mixed_dtypes():
    t = _template(1)
    store = ClientStateStore(t, 4)
    flat = store.flatten(t)
    assert flat.dtype == jnp.float32 and flat.shape == (store.p,)
    _tree_equal(store.unflatten(flat), t)


def test_store_initializes_every_row_to_template():
    t = _template(2)
    store = ClientStateStore(t, 5)
    for c in (0, 2, 4):
        _tree_equal(store.gather_one(c), t)
    stacked = store.gather([1, 3])
    for i in range(2):
        _tree_equal(jax.tree_util.tree_map(lambda l: l[i], stacked), t)


def test_store_rejects_leaves_without_exact_carrier():
    """complex leaves have no exact f32/int32 carrier; zero clients is
    a config error.  (int/bool leaves are FINE — the sidecar segment.)"""
    with pytest.raises(TypeError):
        ClientStateStore({"c": jnp.asarray([1 + 2j], jnp.complex64)}, 2)
    with pytest.raises(ValueError):
        ClientStateStore(_template(), 0)


def _int_template(seed=0):
    """Mixed float + non-float pytree: every non-float leaf dtype the
    int32 sidecar must carry exactly."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(3, 2)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(4,)).astype(np.float32)
                         ).astype(jnp.bfloat16),
        "step": jnp.int32(int(rng.integers(0, 1000))),
        "mask": jnp.asarray(rng.integers(0, 2, size=(5,)).astype(bool)),
        "i8": jnp.asarray(rng.integers(-128, 128, size=(3,)), jnp.int8),
        "u16": jnp.asarray(rng.integers(0, 2 ** 16, size=(2,)),
                           jnp.uint16),
        "u32": jnp.asarray([2 ** 31 + 5, 3], jnp.uint32),  # > int32 max
    }


def test_store_int_bool_leaves_roundtrip_exactly():
    t = _int_template(40)
    store = ClientStateStore(t, 4)
    assert store.pi > 0
    _tree_equal(store.gather_one(1), t)
    frow, irow = store.flatten(t)
    assert frow.dtype == jnp.float32 and frow.shape == (store.p,)
    assert irow.dtype == jnp.int32 and irow.shape == (store.pi,)
    _tree_equal(store.unflatten((frow, irow)), t)
    t2 = _int_template(41)
    store.scatter_params([0, 2], t2)
    _tree_equal(store.gather_one(2), t2)
    _tree_equal(store.gather_one(3), t)
    stacked = store.gather([2, 3])
    _tree_equal(jax.tree_util.tree_map(lambda l: l[0], stacked), t2)
    _tree_equal(jax.tree_util.tree_map(lambda l: l[1], stacked), t)


def test_store_int_leaf_merge_scatter_matches_dict_merge():
    """The fused merge over a mixed float/int tree must equal the dict
    path's staleness_weighted_merge bit for bit — int leaves ride the
    same cast-through-f32 merge, then land back in the sidecar."""
    g = _int_template(42)
    store = ClientStateStore(g, 6)
    stacked = _stack([_int_template(50 + i) for i in range(3)])
    alphas = [0.5, 0.0, 0.25]
    coef = staleness_merge_coefficients(alphas)
    new_params, _ = store.merge_scatter([0, 2, 4], stacked, coef, g)
    want = staleness_weighted_merge(g, stacked, alphas)
    _tree_equal(new_params, want)
    _tree_equal(store.gather_one(2), new_params)
    _tree_equal(store.gather_one(1), g)


def test_scatter_params_targets_only_given_rows():
    t0, t1 = _template(3), _template(4)
    store = ClientStateStore(t0, 6)
    row = store.scatter_params([1, 4], t1)
    assert row.shape == (store.p,)
    _tree_equal(store.gather_one(1), t1)
    _tree_equal(store.gather_one(4), t1)
    _tree_equal(store.gather_one(0), t0)
    _tree_equal(store.gather_one(5), t0)


def test_gather_duplicate_and_padded_ids():
    t0, t1 = _template(5), _template(6)
    store = ClientStateStore(t0, 4)
    store.scatter_params([2], t1)
    stacked = store.gather([2, 2, 0, 2])       # duplicates = pad slots
    row = lambda i: jax.tree_util.tree_map(lambda l: l[i], stacked)
    _tree_equal(row(0), t1)
    _tree_equal(row(1), t1)
    _tree_equal(row(2), t0)
    _tree_equal(row(3), t1)


def test_scatter_flat_row_with_duplicate_ids():
    t0, t1 = _template(7), _template(8)
    store = ClientStateStore(t0, 4)
    store.scatter([3, 3, 1], store.flatten(t1))
    _tree_equal(store.gather_one(3), t1)
    _tree_equal(store.gather_one(1), t1)
    _tree_equal(store.gather_one(0), t0)


# ---------------------------------------------------------------------------
# fused merge + scatter
# ---------------------------------------------------------------------------

def test_merge_scatter_matches_folded_merge_bitwise():
    rng = np.random.default_rng(9)
    g = _template(9)
    store = ClientStateStore(g, 8)
    stacked = _stack([_template(20 + i) for i in range(4)])
    alphas = [0.6, 0.3, 0.0, 0.45]             # one masked straggler
    coef = staleness_merge_coefficients(alphas)
    new_params, new_g = store.merge_scatter([0, 2, 5, 7], stacked, coef, g)
    want = staleness_weighted_merge(g, stacked, alphas)
    _tree_equal(new_params, want)
    # merged clients' rows now hold the new global; others untouched
    for c in (0, 2, 5, 7):
        _tree_equal(store.gather_one(c), new_params)
    _tree_equal(store.gather_one(1), g)
    np.testing.assert_array_equal(np.asarray(new_g),
                                  np.asarray(store.flatten(new_params)))


def test_merge_scatter_zero_coef_pad_rows_are_exact_noops():
    """Padded rows (repeat-last ids, coefficient 0) must not change the
    merge by a single bit — the engine's fused-window convention."""
    g = _template(10)
    alphas = [0.5, 0.25, 0.7]
    trees = [_template(30 + i) for i in range(3)]
    coef = staleness_merge_coefficients(alphas)

    s1 = ClientStateStore(g, 8)
    p1, _ = s1.merge_scatter([1, 2, 3], _stack(trees), coef, g)

    s2 = ClientStateStore(g, 8)
    padded = _stack(trees + [trees[-1]])       # engine edge padding
    coef_pad = np.concatenate([coef, np.zeros(1, np.float32)])
    p2, _ = s2.merge_scatter([1, 2, 3, 3], padded, coef_pad, g)
    _tree_equal(p1, p2)


def test_merge_scatter_masks_nonfinite_zero_coef_rows():
    g = _template(11)
    store = ClientStateStore(g, 4)
    bad = jax.tree_util.tree_map(lambda l: l * np.nan, _template(12))
    stacked = _stack([_template(13), bad])
    alphas = [0.4, 0.0]                        # nan row fully masked
    coef = staleness_merge_coefficients(alphas)
    new_params, _ = store.merge_scatter([0, 1], stacked, coef, g)
    for l in jax.tree_util.tree_leaves(new_params):
        assert np.isfinite(np.asarray(l, np.float32)).all()


def test_repeated_inplace_updates_no_use_after_donate():
    """scatter/merge_scatter donate the buffer on every backend: the
    old buffer is deleted, and the store must rebind and keep serving
    gathers across many cycles."""
    g = _template(14)
    store = ClientStateStore(g, 6)
    params = g
    for it in range(5):
        t = _template(40 + it)
        old = store.bufs
        store.scatter_params([it % 6], t)
        assert all(b.is_deleted() for b in old)
        stacked = _stack([t, _template(50 + it)])
        coef = staleness_merge_coefficients([0.5, 0.25])
        params, _ = store.merge_scatter([it % 6, (it + 1) % 6], stacked,
                                        coef, params)
        _tree_equal(store.gather_one(it % 6), params)
    assert store.buffer.shape == (6, store.p)


# ---------------------------------------------------------------------------
# device-side all-masked round guard
# ---------------------------------------------------------------------------

def test_aggregate_or_keep_all_masked_returns_params():
    params = {"w": jnp.asarray([1.0, 2.0], jnp.float32)}
    stacked = {"w": jnp.asarray([[9.0, 9.0], [np.nan, np.inf]],
                                jnp.float32)}
    out = aggregate_or_keep(params, stacked, np.zeros(2, np.float32))
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(params["w"]))


def test_aggregate_or_keep_matches_weighted_average_when_unmasked():
    from repro.core.aggregation import weighted_average_stacked
    rng = np.random.default_rng(15)
    params = {"w": jnp.asarray(rng.normal(size=(3,)).astype(np.float32))}
    stacked = {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))}
    w = np.asarray([1.0, 0.0, 2.0, 0.5], np.float32)
    out = aggregate_or_keep(params, stacked, w)
    want = weighted_average_stacked(stacked, w)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(want["w"]))


def test_train_round_all_masked_weights_keeps_params():
    class T:
        class cfg:
            arch_id = "t"

        def local_train(self, params, client_id, rnd_seed):
            return {"w": params["w"] + client_id + 1.0}, 10.0

    eng = make_engine(T())
    p = {"w": jnp.zeros(3, jnp.float32)}
    out = eng.train_round(p, [0, 1], 0, weights=[0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(out["w"]), np.zeros(3))
    assert eng.train_round(p, [], 0) is p      # empty cohort: host early-out


# ---------------------------------------------------------------------------
# trainers for the history-parity gates (no model-compile cost)
# ---------------------------------------------------------------------------

class FakeLoopTrainer:
    """Deterministic linear updates, looped path only (exercises the
    store's gather_one + stacked-fallback merge)."""

    class cfg:
        arch_id = "fake"

    def init_params(self, seed=0):
        return {"w": jnp.zeros(3, jnp.float32)}

    def local_train(self, params, client_id, rnd_seed):
        return {"w": params["w"] + (client_id + 1.0)}, 10.0 + client_id

    def evaluate(self, params):
        return float(np.clip(np.mean(np.asarray(params["w"])) / 100.0,
                             0.0, 1.0))


# the shared synthetic cohort trainer (mixed-dtype default tree)
# exercises the store's fused gather -> cohort train -> merge+scatter
# hot path without CNN compile cost
TinyCohortTrainer = SyntheticCohortTrainer


def _net(fl):
    return WirelessNetwork(fl.n_clients, fl.tier_delay_means, fl.delay_std,
                           fl.mu, fl.failure_delay, fl.seed)


def _hist_equal(ha, hb):
    assert ha.rounds == hb.rounds
    assert ha.times == hb.times
    assert ha.accuracy == hb.accuracy
    assert ha.n_selected == hb.n_selected
    assert ha.n_stragglers == hb.n_stragglers


# ---------------------------------------------------------------------------
# acceptance gate: bit-identical histories, store vs dict-of-pytrees
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trainer_cls", [FakeLoopTrainer,
                                         TinyCohortTrainer])
@pytest.mark.parametrize("window,window_secs", [(0, 0.0), (3, 0.0),
                                                (0, 25.0)])
def test_fedasync_store_history_identical_to_dict(trainer_cls, window,
                                                  window_secs):
    fl = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=4, seed=3)
    hs = run_fedasync(trainer_cls(), _net(fl), fl, window=window,
                      window_secs=window_secs, eval_every=4,
                      use_store=True)
    hd = run_fedasync(trainer_cls(), _net(fl), fl, window=window,
                      window_secs=window_secs, eval_every=4,
                      use_store=False)
    _hist_equal(hs, hd)
    if window or window_secs:
        assert hs.meta["mean_cohort"] > 1.0    # windows actually batched


@pytest.mark.parametrize("trainer_cls", [FakeLoopTrainer,
                                         TinyCohortTrainer])
def test_fedbuff_store_history_identical_to_dict(trainer_cls):
    fl = FLConfig(n_clients=6, tau=2, rounds=4, seed=2)
    hs = run_fedbuff(trainer_cls(), _net(fl), fl, window=2, eval_every=8,
                     use_store=True)
    hd = run_fedbuff(trainer_cls(), _net(fl), fl, window=2, eval_every=8,
                     use_store=False)
    _hist_equal(hs, hd)
    assert hs.meta["mean_cohort"] == 2.0


@pytest.mark.parametrize("trainer_cls", [FakeLoopTrainer,
                                         TinyCohortTrainer])
def test_feddct_async_store_history_identical_to_dict(trainer_cls):
    fl = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=6, mu=0.3,
                  seed=5, beta=1.1)
    hs = run_feddct_async(trainer_cls(), _net(fl), fl, use_store=True)
    hd = run_feddct_async(trainer_cls(), _net(fl), fl, use_store=False)
    _hist_equal(hs, hd)
    assert hs.meta["n_drains"] >= 1


def test_engine_train_window_matches_cohort_plus_merge():
    """The fused store window must reproduce the dict path's
    train_cohort + merge_staleness composition bit for bit."""
    tr = TinyCohortTrainer()
    eng = make_engine(tr)
    g = tr.init_params(0)
    starts = [tr.init_params(i + 1) for i in range(3)]
    ids, seeds = [4, 1, 6], [11, 22, 33]
    alphas = [0.5, 0.0, 0.3]

    store = ClientStateStore(g, 8)
    for c, t in zip(ids, starts):
        store.scatter_params([c], t)
    new_params, _ = eng.train_window(store, g, ids, seeds, alphas)

    eng2 = make_engine(tr)
    stacked, _ = eng2.train_cohort(starts, ids, seeds)
    want = eng2.merge_staleness(g, stacked, alphas)
    _tree_equal(new_params, want)


def test_use_store_default_is_windowed_only():
    """Tri-state default: the store engages exactly when windows can
    batch — the pure window=0 sequential loop keeps the dict path's
    free reference rebind (no per-event gather/scatter round-trip)."""
    fl = FLConfig(n_clients=6, tau=2, rounds=2, seed=6)
    h0 = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=0,
                      eval_every=8)
    assert h0.meta["store"] is False
    hw = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=2,
                      eval_every=8)
    assert hw.meta["store"] is True
    hf = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=0,
                      eval_every=8, use_store=True)   # explicit force
    assert hf.meta["store"] is True
    _hist_equal(h0, hf)                               # still identical


class IntLeafTrainer(FakeLoopTrainer):
    """Params carry a non-float leaf (a step counter): lives in the
    store's int32 sidecar segment and round-trips exactly."""

    def init_params(self, seed=0):
        return {"w": jnp.zeros(3, jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    def local_train(self, params, client_id, rnd_seed):
        return {"w": params["w"] + (client_id + 1.0),
                "step": params["step"] + 1}, 10.0 + client_id


def test_int_leaf_template_runs_on_the_store_path():
    """The PR 4 TypeError fallback is gone: a non-float params template
    lives in the store (int32 sidecar) and the history still matches
    the dict reference bit for bit."""
    fl = FLConfig(n_clients=4, tau=2, rounds=2, seed=7)
    hs = run_fedbuff(IntLeafTrainer(), _net(fl), fl, window=2,
                     eval_every=8, use_store=True)
    assert hs.meta["store"] is True
    assert hs.meta["store_path"] == "store"
    hd = run_fedbuff(IntLeafTrainer(), _net(fl), fl, window=2,
                     eval_every=8, use_store=False)
    _hist_equal(hs, hd)


@pytest.mark.parametrize("trainer_cls", [IntLeafTrainer,
                                         TinyCohortTrainer])
def test_kernel_agg_runs_on_the_store_path(trainer_cls):
    """The store's fused merge dispatches the folded Pallas fedagg
    kernel (interpret-mode on CPU): use_kernel_agg + store is the
    default hot path now, bit-identical to the dict reference running
    the same kernel merge."""
    fl = FLConfig(n_clients=6, tau=2, rounds=3, seed=4)
    hk = run_fedbuff(trainer_cls(), _net(fl), fl, window=2,
                     eval_every=8, use_store=True, use_kernel_agg=True)
    assert hk.meta["store"] is True
    assert hk.meta["store_path"] == "store"
    assert hk.meta["kernel_agg"] is True
    hd = run_fedbuff(trainer_cls(), _net(fl), fl, window=2,
                     eval_every=8, use_store=False, use_kernel_agg=True)
    _hist_equal(hk, hd)
    # auto-resolution (use_store=None) now ALSO picks the store when
    # windows batch — kernel agg no longer forces the dict path
    ha = run_fedbuff(trainer_cls(), _net(fl), fl, window=2,
                     eval_every=8, use_kernel_agg=True)
    assert ha.meta["store_path"] == "store"
    assert ha.meta["store_reason"] == "auto-windowed"
    _hist_equal(ha, hd)


def test_kernel_agg_fedasync_and_feddct_async_store_parity():
    """The remaining acceptance-gate methods on the kernel + store
    combination: run_fedasync (windowed) and run_feddct_async."""
    fl = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=4, seed=3)
    hs = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=3,
                      eval_every=4, use_store=True, use_kernel_agg=True)
    hd = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=3,
                      eval_every=4, use_store=False, use_kernel_agg=True)
    _hist_equal(hs, hd)
    assert hs.meta["store_path"] == "store"

    fl2 = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=6, mu=0.3,
                   seed=5, beta=1.1)
    ha = run_feddct_async(TinyCohortTrainer(), _net(fl2), fl2,
                          use_store=True, use_kernel_agg=True)
    hb = run_feddct_async(TinyCohortTrainer(), _net(fl2), fl2,
                          use_store=False, use_kernel_agg=True)
    _hist_equal(ha, hb)
    assert ha.meta["store_path"] == "store"


def test_engine_train_window_kernel_matches_cohort_plus_kernel_merge():
    """Fused store window with kernel dispatch must reproduce the dict
    path's train_cohort + kernel merge_staleness bit for bit — padded
    rows (coef 0) included."""
    tr = TinyCohortTrainer()
    eng = make_engine(tr, use_kernel_agg=True)
    g = tr.init_params(0)
    starts = [tr.init_params(i + 1) for i in range(3)]
    ids, seeds = [4, 1, 6], [11, 22, 33]
    alphas = [0.5, 0.0, 0.3]

    store = ClientStateStore(g, 8)
    for c, t in zip(ids, starts):
        store.scatter_params([c], t)
    new_params, _ = eng.train_window(store, g, ids, seeds, alphas)

    eng2 = make_engine(tr, use_kernel_agg=True)
    stacked, _ = eng2.train_cohort(starts, ids, seeds)
    want = eng2.merge_staleness(g, stacked, alphas)
    _tree_equal(new_params, want)


def test_store_reason_records_resolved_path():
    """Observability: the auto-resolved snapshot path is recorded on
    the RunHistory meta instead of a warning, so benchmarks/tests can
    assert which path actually ran."""
    fl = FLConfig(n_clients=6, tau=2, rounds=2, seed=6)
    h0 = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=0,
                      eval_every=8)
    assert h0.meta["store_path"] == "dict"
    assert h0.meta["store_reason"] == "window0-sequential"
    hoff = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=2,
                        eval_every=8, use_store=False)
    assert hoff.meta["store_path"] == "dict"
    assert hoff.meta["store_reason"] == "forced-off"
    hw = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=2,
                      eval_every=8)
    assert hw.meta["store_path"] == "store"
    assert hw.meta["store_reason"] == "auto-windowed"
    hf = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=0,
                      eval_every=8, use_store=True)
    assert hf.meta["store_reason"] == "forced-on"


# ---------------------------------------------------------------------------
# int8 quantized rows + server-side error feedback (PR 9)
# ---------------------------------------------------------------------------

def _seg_layout(p, rng, max_segs=5):
    """Random contiguous (offset, size) segments covering [0, p)."""
    cuts = sorted(rng.choice(np.arange(1, p), size=min(max_segs - 1,
                                                       p - 1),
                             replace=False).tolist())
    bounds = [0] + cuts + [p]
    return tuple((bounds[i], bounds[i + 1] - bounds[i])
                 for i in range(len(bounds) - 1))


def test_quantize_rows_property_sweep_matches_ref():
    """Seeded sweep of the row quantizer against the numpy oracle:
    exact ops/ref parity, the half-step round-trip bound
    ``|x - dq(q(x))| <= scale/2`` per (row, segment), exact zeros, and
    exact constant segments."""
    rng = np.random.default_rng(123)
    for case in range(6):
        rows, p = int(rng.integers(1, 7)), int(rng.integers(4, 40))
        segs = _seg_layout(p, rng)
        x = rng.normal(size=(rows, p)).astype(np.float32)
        # per-segment magnitude spread: tiny to huge dynamic ranges
        for j, (off, size) in enumerate(segs):
            x[:, off:off + size] *= 10.0 ** float(rng.integers(-3, 4))
        # a constant segment (rng=0 -> exact path) and exact zeros
        off0, size0 = segs[0]
        x[:, off0:off0 + size0] = np.float32(rng.normal())
        zmask = rng.random(size=x.shape) < 0.15
        zmask[:, off0:off0 + size0] = False      # keep seg 0 constant
        x[zmask] = 0.0

        q, m = jax.jit(quantize_rows,
                       static_argnums=(1,))(jnp.asarray(x), segs)
        q, m = np.asarray(q), np.asarray(m)
        qr, mr = quantize_rows_ref(x, segs)
        np.testing.assert_array_equal(q, qr)        # exact ops/ref parity
        np.testing.assert_array_equal(m, mr)
        dq = dequantize_rows_ref(q, m, segs)

        assert q.dtype == np.int8 and m.shape == (rows, 2 * len(segs))
        for j, (off, size) in enumerate(segs):
            scale = m[:, j][:, None]                # (rows, 1)
            err = np.abs(x[:, off:off + size] - dq[:, off:off + size])
            assert (err <= scale * 0.5 * (1 + 1e-4) + 1e-12).all(), \
                f"case {case} seg {j}: round-trip bound violated"
        # exact zero preservation (0 is always on the snapped grid)
        np.testing.assert_array_equal(dq[zmask], 0.0)
        # the constant segment round-trips exactly (scale=1, zp=value)
        np.testing.assert_array_equal(dq[:, off0:off0 + size0],
                                      x[:, off0:off0 + size0])


def test_quant_store_roundtrip_matches_ref_pipeline():
    """Dense quant store: gather returns exactly what the numpy
    quantize->dequantize oracle predicts, for float AND int-sidecar
    templates (the sidecar stays lossless under quant_bits=8)."""
    for tmpl, seed in ((_template, 60), (_int_template, 61)):
        t0, t1 = tmpl(seed), tmpl(seed + 1)
        store = ClientStateStore(t0, 4, quant_bits=8)
        store.scatter_params([1], t1)
        row = store.flatten(t1)
        frow = np.asarray(row[0] if store.pi else row, np.float32)
        q, m = quantize_rows_ref(frow[None], store._fsegs)
        dq = dequantize_rows_ref(q, m, store._fsegs)[0]
        np.testing.assert_array_equal(np.asarray(store.bufs[0][1]), q[0])
        want = store.unflatten((jnp.asarray(dq), row[1])
                               if store.pi else jnp.asarray(dq))
        _tree_equal(store.gather_one(1), want)
        # int/bool leaves specifically: still bit-exact vs the input
        got = store.gather_one(1)
        for k, leaf in t1.items():
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(leaf))
        # untouched rows still serve the (quantized) template
        _tree_equal(store.gather_one(0), store.gather_one(3))


def test_quant_store_error_feedback_residual_and_addback():
    """EF contract: after scatter of row ``x`` the stored residual is
    exactly ``x - dq(q(x))``; the NEXT scatter quantizes ``x + ef``
    (add-back) and stores the new residual.  EF off keeps no state."""
    t0, t1 = _template(70), _template(71)
    store = ClientStateStore(t0, 4, quant_bits=8)
    assert store.error_feedback
    frow = np.asarray(store.flatten(t1), np.float32)

    store.scatter_params([2], t1)
    q1, m1 = quantize_rows_ref(frow[None], store._fsegs)
    dq1 = dequantize_rows_ref(q1, m1, store._fsegs)[0]
    ef1 = np.asarray(store.ef_residual(2))
    np.testing.assert_array_equal(ef1, frow - dq1)

    store.scatter_params([2], t1)                  # round 2: same update
    x2 = frow + ef1
    q2, m2 = quantize_rows_ref(x2[None], store._fsegs)
    dq2 = dequantize_rows_ref(q2, m2, store._fsegs)[0]
    np.testing.assert_array_equal(np.asarray(store.ef_residual(2)),
                                  x2 - dq2)
    np.testing.assert_array_equal(np.asarray(store.bufs[0][2]), q2[0])
    assert store.bytes_by_tier()["ef"] == 4 * store.p

    s2 = ClientStateStore(t0, 4, quant_bits=8, error_feedback=False)
    s2.scatter_params([1], t1)
    assert s2.ef_residual(1) is None
    np.testing.assert_array_equal(np.asarray(s2.bufs[0][1]), q1[0])
    assert s2.bytes_by_tier()["ef"] == 0


def test_quant_store_validation_and_byte_accounting():
    with pytest.raises(ValueError):
        ClientStateStore(_template(), 4, quant_bits=4)
    with pytest.raises(ValueError):                # needs a float leaf
        ClientStateStore({"step": jnp.zeros((), jnp.int32)}, 4,
                         quant_bits=8)
    t = _int_template(80)
    s8 = ClientStateStore(t, 4, quant_bits=8)
    s32 = ClientStateStore(t, 4)
    from repro.core.state import wire_bytes
    assert s8.wire_bytes_per_update == wire_bytes(t, 8)
    assert s32.wire_bytes_per_update == wire_bytes(t, 32)
    assert s8.wire_bytes_per_update < s32.wire_bytes_per_update
    # hot bytes shrink ~4x on the float segment (int sidecar unchanged)
    b8, b32 = s8.bytes_by_tier(), s32.bytes_by_tier()
    assert b8["hot"] < b32["hot"]
    assert b8["hot"] == 4 * (s8.p + 8 * len(s8._fsegs) + 4 * s8.pi)


def test_quant32_explicit_is_bit_identical_to_default_matrix():
    """``quant_bits=32`` IS the existing store path: explicit 32 must
    stay bit-identical to the default run and the dict reference."""
    fl = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=4, seed=3)
    base = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=3,
                        eval_every=4, use_store=True)
    h32 = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=3,
                       eval_every=4, use_store=True, quant_bits=32)
    hd = run_fedasync(TinyCohortTrainer(), _net(fl), fl, window=3,
                      eval_every=4, use_store=False)
    _hist_equal(base, h32)
    _hist_equal(h32, hd)
    assert h32.meta["quant_bits"] == 32

    fl2 = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=6, mu=0.3,
                   seed=5, beta=1.1)
    a = run_feddct_async(TinyCohortTrainer(), _net(fl2), fl2,
                         use_store=True)
    b = run_feddct_async(TinyCohortTrainer(), _net(fl2), fl2,
                         use_store=True, quant_bits=32)
    _hist_equal(a, b)


def test_quant8_seeded_deterministic_and_meta():
    """Quantized runs are seeded-deterministic (same seed -> identical
    history) and the meta records what ran; the run may differ from f32
    (gated convergence delta, NOT bit-identity)."""
    fl = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=6, mu=0.3,
                  seed=5, beta=1.1)
    ha = run_feddct_async(TinyCohortTrainer(), _net(fl), fl, quant_bits=8)
    hb = run_feddct_async(TinyCohortTrainer(), _net(fl), fl, quant_bits=8)
    _hist_equal(ha, hb)
    assert ha.meta["quant_bits"] == 8
    assert ha.meta["error_feedback"] is True
    assert ha.meta["store"] is True
    assert ha.meta["bytes_up"] > 0
    hf = run_feddct_async(TinyCohortTrainer(), _net(fl), fl,
                          use_store=True)
    assert ha.meta["wire_bytes_per_update"] \
        < hf.meta["wire_bytes_per_update"]
    assert ha.meta["store_bytes_hot"] < hf.meta["store_bytes_hot"]
    # quant8 cannot run without the store (the dict path has no rows)
    with pytest.raises(ValueError):
        run_feddct_async(TinyCohortTrainer(), _net(fl), fl, quant_bits=8,
                         use_store=False)


def test_error_feedback_cancels_accumulated_quantization_bias():
    """What EF buys — and what running WITHOUT it measurably costs.
    For a slowly-drifting row (drift far below the grid step),
    deterministic rounding repeats nearly the same error on every
    write, so the stored rows' accumulated error grows linearly
    without EF; with EF it telescopes to the one outstanding residual
    (``dq_t - x_t = ef_{t-1} - ef_t``), bounded by half a grid step."""
    t = _template(90)
    se = ClientStateStore(t, 2, quant_bits=8)
    sn = ClientStateStore(t, 2, quant_bits=8, error_feedback=False)
    frow0 = np.asarray(se.flatten(t), np.float32)
    errs_e = np.zeros_like(frow0)
    errs_n = np.zeros_like(frow0)
    for i in range(60):
        x = frow0 * np.float32(1.0 + i * 1e-5)
        for s, errs in ((se, errs_e), (sn, errs_n)):
            s.scatter([0], jnp.asarray(x))
            dq = dequantize_rows_ref(np.asarray(s.bufs[0][0])[None],
                                     np.asarray(s.bufs[1][0])[None],
                                     s._fsegs)[0]
            errs += dq - x
    assert 5.0 * np.abs(errs_e).mean() < np.abs(errs_n).mean()


def test_quant8_dense_tiered_host_disk_histories_identical(tmp_path):
    """Residency stays pure data movement under quantized rows: dense
    vs tiered-host vs tiered-disk at capacity < N are bit-identical,
    with identical modeled uplink."""
    fl = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=6, mu=0.3,
                  seed=5, beta=1.1)
    hd = run_feddct_async(TinyCohortTrainer(), _net(fl), fl, quant_bits=8)
    hh = run_feddct_async(TinyCohortTrainer(), _net(fl), fl, quant_bits=8,
                          store_capacity=3)
    hk = run_feddct_async(TinyCohortTrainer(), _net(fl), fl, quant_bits=8,
                          store_capacity=3, store_cold_dir=str(tmp_path))
    _hist_equal(hd, hh)
    _hist_equal(hd, hk)
    assert hd.meta["bytes_up"] == hh.meta["bytes_up"] \
        == hk.meta["bytes_up"]
    assert hh.meta["store_bytes_cold"] > 0
    assert hk.meta["store_bytes_cold"] > 0


@pytest.mark.slow
def test_fedasync_windowed_cnn_store_history_identical_to_dict():
    from repro.config import get_arch
    from repro.fl.client import CNNTrainer
    fl = FLConfig(n_clients=8, n_tiers=4, tau=2, rounds=3, mu=0.0,
                  primary_frac=0.7, seed=0, lr=0.003)
    tr = CNNTrainer(get_arch("cnn-mnist").reduced(), fl, "mnist",
                    scale=0.01)
    hs = run_fedasync(tr, _net(fl), fl, window_secs=15.0, eval_every=4,
                      use_store=True)
    hd = run_fedasync(tr, _net(fl), fl, window_secs=15.0, eval_every=4,
                      use_store=False)
    _hist_equal(hs, hd)
    assert hs.meta["mean_cohort"] > 1.0


@pytest.mark.slow
def test_feddct_async_quant8_cnn_convergence_gate():
    """The quantized-run convergence contract on a seeded CNN task:
    int8+EF tracks the f32 run within 1.0 accuracy point (best-acc
    over the run), while actually quantizing (the trajectory is NOT
    bit-identical to f32) and with EF live (EF on/off trajectories
    diverge).  The accumulated-bias cost of running WITHOUT EF is
    asserted deterministically in
    test_error_feedback_cancels_accumulated_quantization_bias —
    accuracy at test scale is too noisy to resolve it."""
    from repro.config import get_arch
    from repro.fl.client import CNNTrainer
    fl = FLConfig(n_clients=8, n_tiers=2, tau=2, rounds=40, mu=0.0,
                  primary_frac=0.7, seed=0, lr=0.003)

    def trainer():
        return CNNTrainer(get_arch("cnn-mnist").reduced(), fl, "mnist",
                          scale=0.05)

    h32 = run_feddct_async(trainer(), _net(fl), fl, use_store=True)
    h8 = run_feddct_async(trainer(), _net(fl), fl, quant_bits=8)
    h8n = run_feddct_async(trainer(), _net(fl), fl, quant_bits=8,
                           error_feedback=False)
    assert abs(max(h32.accuracy) - max(h8.accuracy)) <= 0.01 + 1e-9
    assert h8.accuracy != h32.accuracy        # quantization is active
    assert h8.accuracy != h8n.accuracy        # error feedback is live
    assert h8.meta["quant_bits"] == 8
    assert h8.meta["bytes_up"] < h32.meta["bytes_up"]
