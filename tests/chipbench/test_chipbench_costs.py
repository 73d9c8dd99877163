"""What the benchmark counts from shapes: the model FLOPs of each
configuration against XLA's own count of one client step, the fedagg
merge's needed bytes (real rows, never padded ones), and the plain
reference models against the program's forward pass."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import ROOT

from chipbench import costs, harness
from chipbench import reference as ref

CONFIGS = ["cnn-mnist"]


def _config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


def test_cnn_mnist_count():
    # 2 convs (28x28x1->32, 14x14x32->64) + 3136x512 + 512x10, x3
    assert costs.flops_per_sample(_config("cnn-mnist")) == 32_695_296


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_against_xla(name):
    """XLA's count of one client's forward+backward at batch 10 exceeds
    the benchmark's by the elementwise work it leaves out (bias, relu,
    pooling, normalization, loss) and falls short by the input gradient
    of the first convolution, which no step computes."""
    from repro.config import get_arch
    from repro.models.cnn import cnn_loss
    cfg = _config(name)
    arch = get_arch(cfg["arch"])
    params = harness.make_weights(cfg, 0)
    h, w, c = cfg["sizes"]["input_hw"]
    x = jnp.zeros((10, h, w, c), jnp.float32)
    y = jnp.zeros((10,), jnp.int32)
    step = jax.jit(jax.grad(lambda p: cnn_loss(arch, p, {"x": x, "y": y})))
    xla = step.lower(params).compile().cost_analysis()
    xla = xla[0] if isinstance(xla, list) else xla
    ours = 10 * costs.flops_per_sample(cfg)
    assert abs(xla["flops"] - ours) / ours < 0.08, (xla["flops"], ours)


def test_training_flops():
    cfg = _config("cnn-mnist")
    assert costs.training_flops(cfg, 5) == 5 * 120 * 10 * 32_695_296


def test_fedagg_bytes_count_real_rows():
    p = 1_630_090
    assert costs.fedagg_bytes(5, p) == 6 * p * 4
    # a round of 5 survivors trains a bucket of 8; the merge needs 5; a
    # round with none merges nothing
    assert costs.fedagg_window_bytes([5, 0, 1], p) == (6 + 2) * p * 4
    assert harness.RoundRecorder.distinct([4, 9, 2, 2, 2, 2, 2, 2]) == 3


def test_fedagg_fold_bytes_count_real_rows_and_the_global():
    """A fold reads each real row and the global once and writes one
    row; a round of one row merges without the kernel and counts
    nothing; padded rows count nothing."""
    p = 1_630_090
    assert costs.fedagg_fold_bytes(5, p) == 7 * p * 4
    assert costs.fold_window_bytes([5, 0, 1, 2], p) == (7 + 4) * p * 4
    assert costs.fold_window_bytes([1, 1, 0], p) == 0


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_loss_is_the_cross_entropy_of_forward(name):
    cfg = _config(name)
    model = ref.model_module(cfg["model"])
    params = harness.make_weights(cfg, 3)
    h, w, c = cfg["sizes"]["input_hw"]
    x = jax.random.normal(jax.random.PRNGKey(2), (4, h, w, c))
    y = jnp.asarray([0, 3, 9, 3], jnp.int32)
    logits = model.forward(cfg["sizes"], params, x, ref.HIGHEST)
    want = -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(4), y])
    got = model.loss(cfg["sizes"], params, x, y, ref.HIGHEST)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_forward_matches_program(name):
    from repro.config import get_arch
    from repro.models.cnn import cnn_forward
    cfg = _config(name)
    model = ref.model_module(cfg["model"])
    params = harness.make_weights(cfg, 5)
    h, w, c = cfg["sizes"]["input_hw"]
    x = jax.random.normal(jax.random.PRNGKey(1), (4, h, w, c))
    with jax.default_matmul_precision("highest"):
        got = model.forward(cfg["sizes"], params, x, ref.HIGHEST)
        want = cnn_forward(get_arch(cfg["arch"]), params, x, im2col=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_have_the_program_layout(name):
    from repro.config import get_arch
    from repro.models.cnn import init_cnn
    cfg = _config(name)
    ours = harness.make_weights(cfg, 2 ** 33 + 5)
    theirs = jax.eval_shape(lambda: init_cnn(get_arch(cfg["arch"]),
                                             jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    leaves = jax.tree_util.tree_leaves(ours)
    assert [l.shape for l in leaves] == \
        [l.shape for l in jax.tree_util.tree_leaves(theirs)]
    assert sum(l.size for l in leaves) == cfg["n_params"]
    assert len(leaves) == cfg["n_leaves"]
