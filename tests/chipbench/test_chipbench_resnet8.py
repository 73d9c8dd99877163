"""The CIFAR-10 ResNet8 configuration: the plain reference against the
program's model (forward, loss, gradients, one cohort of local
training) on seeded random weights at a small width (channels 4/8/16)
on the CPU, the published sizes from shapes alone, the benchmark's copy
of the CIFAR-10 samples against the program's generator, the named
scopes in the compiled training program, and a ``resnet8-cifar10.sync``
run cut to a CPU's size through the harness: correct as the program
runs, not correct with the bfloat16 control or half of each batch put
in the program's place."""

import contextlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import ROOT

from chipbench import costs, harness
from chipbench import reference as ref
from chipbench.datasets import cifar10, images
from chipbench.models import resnet8

CELL = "resnet8-cifar10.sync"
CFG = json.loads((ROOT / "chipbench" / "configs" / "resnet8-cifar10.json")
                 .read_text())
SMALL = {"input_hw": [32, 32, 3], "kernel": 3, "channels": [4, 8, 16],
         "strides": [1, 2, 2], "n_classes": 10}
SMALL_PARAMS = 5_102          # stem 108, blocks 296 + 912 + 3,616, FC 170


@contextlib.contextmanager
def small_arch():
    """Registers ``resnet8-small``, the program's ResNet8 at channels
    4/8/16, for the length of a test module."""
    from repro.config import base
    base._ensure_loaded()
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(base._REGISTRY, "resnet8-small", lambda: base.ModelConfig(
            arch_id="resnet8-small", family="cnn", cnn_channels=(4, 8, 16),
            cnn_fc=(10,), input_hw=(32, 32, 3), n_classes=10, resnet=True))
        yield


@pytest.fixture(scope="module", autouse=True)
def _arch():
    with small_arch():
        yield


def _small_weights(seed):
    return harness.make_weights({"model": "resnet8", "sizes": SMALL}, seed)


def _program_arch():
    from repro.config import get_arch
    return get_arch("resnet8-small")


def _images(seed, n=6):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 32, 32, 3))


# -- the published sizes, from shapes ----------------------------------------

def test_published_sizes_from_shapes():
    """77,594 params in 17 leaves, in the program's layout; 75,009,792
    training FLOPs a sample (25,003,264 forward: stem 884,736, stages
    9,437,184 + 7,340,032 + 7,340,032, FC 1,280)."""
    from repro.config import get_arch
    from repro.models.cnn import init_cnn
    ours = jax.eval_shape(lambda: resnet8.init(CFG["sizes"],
                                               jax.random.PRNGKey(0)))
    theirs = jax.eval_shape(lambda: init_cnn(get_arch(CFG["arch"]),
                                             jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    leaves = jax.tree_util.tree_leaves(ours)
    assert [l.shape for l in leaves] == \
        [l.shape for l in jax.tree_util.tree_leaves(theirs)]
    assert (sum(l.size for l in leaves), len(leaves)) == (77_594, 17)
    assert (CFG["n_params"], CFG["n_leaves"]) == (77_594, 17)
    assert resnet8.flops_per_sample(CFG["sizes"]) == 75_009_792 == \
        costs.flops_per_sample(CFG)
    assert costs.training_flops(CFG, 1) == 100 * 10 * 75_009_792


def test_small_sizes_and_strides_are_the_programs():
    leaves = jax.tree_util.tree_leaves(_small_weights(0))
    assert sum(l.size for l in leaves) == SMALL_PARAMS and len(leaves) == 17
    assert _program_arch().cnn_channels == tuple(SMALL["channels"])


def test_flops_against_xla():
    """XLA's count of one client's forward+backward at batch 10 exceeds
    the benchmark's by the elementwise work it leaves out (norms, relus,
    residual adds, pooling, loss) and falls short by the input gradient
    of the stem, which no step computes."""
    from repro.config import get_arch
    from repro.models.cnn import cnn_loss
    arch = get_arch(CFG["arch"])
    params = harness.make_weights(CFG, 0)
    x = jnp.zeros((10, 32, 32, 3), jnp.float32)
    y = jnp.zeros((10,), jnp.int32)
    step = jax.jit(jax.grad(lambda p: cnn_loss(arch, p, {"x": x, "y": y},
                                               im2col=True)))
    xla = step.lower(params).compile().cost_analysis()
    xla = xla[0] if isinstance(xla, list) else xla
    ours = 10 * costs.flops_per_sample(CFG)
    assert abs(xla["flops"] - ours) / ours < 0.12, (xla["flops"], ours)


# -- the reference against the program, small width, highest precision ------
# Both sides compute in float32 at "highest" matmul precision; they sum
# the convolutions' products in other orders (patches + GEMM or a direct
# convolution), so they agree to float32 rounding carried through three
# norms: logits of size ~1 to 2e-6 absolute (a few ulps at 1.0 times
# the ~20 rounding steps of the deepest path), gradients likewise relative
# to their leaf's largest entry.

@pytest.mark.parametrize("im2col", [True, False])
def test_forward_matches_program(im2col):
    from repro.models.cnn import cnn_forward
    params = _small_weights(5)
    x = _images(1)
    with jax.default_matmul_precision("highest"):
        got = resnet8.forward(SMALL, params, x, ref.HIGHEST)
        want = cnn_forward(_program_arch(), params, x, im2col=im2col)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)
    assert float(jnp.max(jnp.abs(want))) > 0.1      # a real comparison


@pytest.mark.parametrize("im2col", [True, False])
def test_loss_and_gradients_match_program(im2col):
    from repro.models.cnn import cnn_loss
    params = _small_weights(7)
    x = _images(2)
    y = jnp.asarray([0, 3, 9, 3, 5, 1], jnp.int32)
    with jax.default_matmul_precision("highest"):
        lo, g = jax.value_and_grad(
            lambda p: resnet8.loss(SMALL, p, x, y, ref.HIGHEST))(params)
        lp, gp = jax.value_and_grad(
            lambda p: cnn_loss(_program_arch(), p, {"x": x, "y": y},
                               im2col=im2col))(params)
    np.testing.assert_allclose(float(lo), float(lp), rtol=1e-6)
    want = -jnp.mean(jax.nn.log_softmax(
        resnet8.forward(SMALL, params, x, ref.HIGHEST))[jnp.arange(6), y])
    np.testing.assert_allclose(float(lo), float(want), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(gp)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * scale, scale


def _small_trainer(n_clients=10, scale=0.02):
    from repro.config.base import FLConfig
    from repro.fl.client import build_fl_clients
    fl = FLConfig(n_clients=n_clients, batch_size=10, local_epochs=1,
                  lr=0.003, optimizer="adam", primary_frac=0.7, seed=0)
    return build_fl_clients("resnet8-small", fl, dataset="cifar10",
                            scale=scale)


def test_cohort_matches_reference_training():
    """One ``_batch_train_impl`` cohort of three clients, five Adam
    steps of batch 10 each, against the reference's ``make_train`` from
    the same start on the same batches.  Adam divides each update by
    the root of its second moment, so a gradient entry near zero turns
    float32 rounding into a step of up to ``lr``: rows are compared by
    the norm of the change of each leaf, to 1e-4 of the reference's."""
    trainer = _small_trainer()
    params = _small_weights(11)
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.normal(size=(3, 5, 10, 32, 32, 3)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, (3, 5, 10)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = trainer._batch_train(params, xs, ys)
        starts = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (3,) + l.shape), params)
        want = ref.make_train(resnet8, SMALL, 0.003)(starts, xs, ys)
    start = ref.leaves64(params)
    for k in range(3):
        gap, leaf = ref.train_gap(start, ref.row_of(got, k),
                                  ref.row_of(want, k), [True] * 17)
        assert gap < 1e-4, (k, leaf, gap)
        moved = [np.linalg.norm(r - s)
                 for s, r in zip(start, ref.row_of(want, k))]
        assert min(moved) > 0


# -- the benchmark's CIFAR-10 samples ------------------------------------

@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_train_set_matches_the_program(seed):
    from repro.data.synthetic import make_image_dataset
    theirs = make_image_dataset("cifar10", seed=seed, scale=0.02)
    x, y = images.train_set("cifar10", seed, 0.02)
    assert x.shape == (1000, 32, 32, 3)
    assert x.dtype == theirs["x_train"].dtype
    assert y.dtype == theirs["y_train"].dtype
    assert np.array_equal(x, theirs["x_train"])
    assert np.array_equal(y, theirs["y_train"])


def test_data_gap_reads_zero_against_the_programs_trainer():
    trainer = _small_trainer()
    cfg = dict(CFG, data_scale=0.02, samples_per_client=100, local_steps=10,
               federation=dict(CFG["federation"], n_clients=10))
    ours = cifar10.clients(cfg)
    assert cifar10.data_gap(trainer, ours) == 0
    assert cifar10.check(cfg, trainer) is None
    assert [cifar10.n_samples(c) for c in ours] == [100] * 10
    xs, ys = cifar10.client_stream(cfg, ours[4], 12)
    assert xs.shape == (10, 10, 32, 32, 3) and ys.shape == (10, 10)
    rows = trainer._client_epoch_rows(4, 12)
    table_x = np.asarray(trainer._tables[None][0])
    assert np.array_equal(table_x[rows].reshape(xs.shape), xs)


# -- named scopes ----------------------------------------------------------

def _train_program_text(trainer, params, hw):
    h, w, c = hw
    xs = jnp.zeros((1, 1, 2, h, w, c), jnp.float32)
    ys = jnp.zeros((1, 1, 2), jnp.int32)
    return trainer._batch_train.lower(params, xs, ys).compile().as_text()


SCOPES = (["resnet.stem", "resnet.head"]
          + [f"resnet.block{i}.{part}" for i in range(3)
             for part in ("conv1", "norm1", "conv2", "norm2")]
          + ["resnet.block1.shortcut", "resnet.block2.shortcut"])


def test_resnet_training_program_carries_the_scopes():
    """Every part of the net names the ops it compiles to (block 0's
    shortcut is the identity, no op of its own)."""
    text = _train_program_text(_small_trainer(), _small_weights(0),
                               SMALL["input_hw"])
    # a scope is a component of the op's name path: "a/resnet.stem/conv",
    # or "transpose(jvp(resnet.stem))/..." on the gradient's ops
    names = re.findall(r'op_name="([^"]*)"', text)
    missing = [s for s in SCOPES if not any(
        re.search(rf"[/(]{re.escape(s)}[/)]", n) for n in names)]
    assert names and not missing, missing


def test_plain_cnn_training_program_carries_no_resnet_scope():
    from repro.config.base import FLConfig
    from repro.fl.client import build_fl_clients
    fl = FLConfig(n_clients=4, batch_size=10, local_epochs=1, lr=0.003,
                  optimizer="adam", seed=0)
    trainer = build_fl_clients("cnn-mnist", fl, dataset="mnist", scale=0.01)
    params = trainer.init_params(0)
    text = _train_program_text(trainer, params, (28, 28, 1))
    assert "op_name=" in text and "resnet." not in text


# -- the cell, cut to a CPU's size -------------------------------------------

def small_cell() -> dict:
    """``resnet8-cifar10.sync`` at channels 4/8/16 over 10 clients of 100
    synthetic CIFAR-10 samples (10 local steps), with its own limits."""
    from chipbench import spec
    cell = spec.cell(CELL)
    cfg = cell["config_data"]
    cfg.update(arch="resnet8-small", data_scale=0.02, sizes=dict(SMALL),
               n_params=SMALL_PARAMS, samples_per_client=100, local_steps=10)
    cfg["federation"].update(n_clients=10, tau=3, n_tiers=2,
                             tier_delay_means=[5.0, 10.0])
    return cell


def test_small_cell_is_correct_and_its_stand_ins_are_not():
    """One run, then the bfloat16 control and half of each batch put in
    the program's place on the rounds it captured: each fails a limit.
    The control needs a captured round that merged two rows or more (a
    one-row merge of bfloat16 rows is exact), which a 5-s window holds."""
    from chipbench import calibrate
    cell = small_cell()
    limits = cell["limits"]["limits"]
    (line,) = calibrate.calibrate(cell, [2 ** 31 + 17], 5.0,
                                  ["control", "half_batch"], lambda m: None,
                                  require_tpu=False)
    assert line["correct"] is True, line["program"]
    assert line["program"]["data_gap"] == 0
    assert all(line["program"][k] <= v for k, v in limits.items()
               if k in line["program"])
    for kind in ("control", "half_batch"):
        assert any(line[kind][k] > limits[k] for k in line[kind]), (kind,
                                                                    line)
