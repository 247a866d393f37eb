"""DP train/eval step semantics on the virtual 8-device mesh.

The key correctness property (SURVEY.md §2 "Parallelism strategies"): 8-way
data parallelism must compute the SAME update as single-device training on
the full global batch — that is what Horovod's averaged allreduce guarantees
in the reference, and what XLA's sharding propagation must reproduce here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.analysis.program_audit import primitive_counts
from distributeddeeplearning_tpu.data.synthetic import synthetic_batch
from distributeddeeplearning_tpu.models import get_model
from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh, shard_batch
from distributeddeeplearning_tpu.train.schedule import goyal_lr_schedule
from distributeddeeplearning_tpu.train.state import create_train_state, sgd_momentum
from distributeddeeplearning_tpu.train.step import (
    build_eval_step,
    build_train_step,
    classification_metrics,
    cross_entropy_loss,
    place_state,
    topk_correct,
)
from distributeddeeplearning_tpu.utils.metrics import label_in_topk

IMG = (32, 32, 3)
NCLS = 11


def _make_state(lr=0.1, seed=0):
    model = get_model("resnet18", num_classes=NCLS, dtype=jnp.float32)
    tx = sgd_momentum(optax.constant_schedule(lr), weight_decay=5e-5)
    return create_train_state(
        jax.random.key(seed), model, (8, *IMG), tx
    )


@pytest.fixture(scope="module")
def mesh8():
    return create_mesh(MeshSpec())


def test_loss_decreases_on_fixed_batch(mesh8):
    state = _make_state()
    step = build_train_step(mesh8, state, compute_dtype=jnp.float32)
    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    state, first = step(state, batch)
    for _ in range(5):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < float(first["loss"])


def test_dp_equals_single_device():
    """The allreduce contract: same batch, 8-way sharded vs 1 device."""
    batch_np = synthetic_batch(16, IMG, NCLS, seed=3)

    mesh8 = create_mesh(MeshSpec())
    state8 = _make_state(seed=1)
    step8 = build_train_step(mesh8, state8, compute_dtype=jnp.float32)
    _, m8 = step8(state8, shard_batch(mesh8, batch_np))

    mesh1 = create_mesh(devices=jax.devices()[:1])
    state1 = _make_state(seed=1)
    step1 = build_train_step(mesh1, state1, compute_dtype=jnp.float32)
    _, m1 = step1(state1, shard_batch(mesh1, batch_np))

    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]), rtol=1e-4)
    np.testing.assert_allclose(float(m8["top5"]), float(m1["top5"]), rtol=1e-5)


def test_metrics_shape_and_keys(mesh8):
    state = _make_state()
    sched = goyal_lr_schedule(0.0125, 8, 10)
    step = build_train_step(mesh8, state, schedule=sched, compute_dtype=jnp.float32)
    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    _, metrics = step(state, batch)
    assert set(metrics) == {"loss", "top1", "top5", "lr"}
    for v in metrics.values():
        assert v.shape == ()
        assert jnp.isfinite(v)


def test_state_step_increments(mesh8):
    state = _make_state()
    step = build_train_step(mesh8, state, compute_dtype=jnp.float32)
    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    new_state, _ = step(state, batch)
    assert int(new_state.step) == 1


def test_batch_stats_update(mesh8):
    state = _make_state()
    step = build_train_step(mesh8, state, compute_dtype=jnp.float32)
    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    old = jax.tree_util.tree_leaves(state.batch_stats)[0].copy()
    new_state, _ = step(state, batch)
    new = jax.tree_util.tree_leaves(new_state.batch_stats)[0]
    assert not np.allclose(np.asarray(old), np.asarray(new))


def test_eval_step_does_not_mutate(mesh8):
    state = _make_state()
    ev = build_eval_step(mesh8, state, compute_dtype=jnp.float32)
    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    metrics = ev(state, batch)
    assert set(metrics) == {"loss", "top1", "top5"}


def test_cross_entropy_matches_reference_formula():
    logits = jnp.array([[2.0, 0.0, -1.0], [0.0, 3.0, 0.5]])
    labels = jnp.array([0, 1])
    expected = -np.mean(
        [
            np.log(np.exp(2.0) / np.exp([2.0, 0.0, -1.0]).sum()),
            np.log(np.exp(3.0) / np.exp([0.0, 3.0, 0.5]).sum()),
        ]
    )
    np.testing.assert_allclose(float(cross_entropy_loss(logits, labels)), expected, rtol=1e-6)


def test_topk_accuracy():
    logits = jnp.array([[0.1, 0.9, 0.0], [0.8, 0.1, 0.1]])
    labels = jnp.array([1, 2])
    assert float(topk_correct(logits, labels, 1)) == pytest.approx(0.5)
    assert float(topk_correct(logits, labels, 3)) == pytest.approx(1.0)


TOPK_CLASSES = 11


def _topk_case(shape, tie, dtype):
    """(logits, labels) for one case.  ``tie``: "none" leaves the logits
    continuous; else they are quantised to half-integers, so most rows tie
    (and some hold -0.0 beside 0.0), and each label is moved to the lowest,
    a middle or the highest index that ties with it."""
    rng = np.random.default_rng(7)
    if shape == "2d":
        x = rng.normal(size=(40, TOPK_CLASSES))
        y = rng.integers(0, TOPK_CLASSES, size=(40,))
    else:
        x = rng.normal(size=(4, 11, TOPK_CLASSES))
        y = rng.integers(0, TOPK_CLASSES, size=(4, 11))
    if tie != "none":
        x = np.round(x * 2) / 2
        tied = x == np.take_along_axis(x, y[..., None], -1)
        assert (tied.sum(-1) > 1).mean() > 0.5  # most labels sit on a tie
        ranks = np.cumsum(tied, -1)
        pick = {"lowest": np.ones_like(y), "highest": tied.sum(-1),
                "middle": (tied.sum(-1) + 1) // 2}[tie]
        y = np.argmax(tied & (ranks == pick[..., None]), -1)
    logits = jnp.asarray(x, dtype)
    labels = jnp.asarray(y, jnp.int32)
    if shape == "3d-slice":
        # the LM call: shifted logits against shifted tokens, kept 3-D
        return logits[:, :-1], labels[:, 1:]
    return logits, labels


@pytest.mark.parametrize("tie", ["none", "lowest", "middle", "highest"])
@pytest.mark.parametrize("shape", ["2d", "3d", "3d-slice"])
@pytest.mark.parametrize("k", [1, 5, TOPK_CLASSES, TOPK_CLASSES + 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_correct_matches_lax_top_k(dtype, k, shape, tie):
    """The compare-and-count body decides every position as ``lax.top_k``
    does, ties included, and returns the same mean."""
    logits, labels = _topk_case(shape, tie, dtype)
    _, top = jax.lax.top_k(
        logits.astype(jnp.float32), min(k, TOPK_CLASSES)
    )
    want = (top == labels[..., None]).any(-1)
    np.testing.assert_array_equal(
        np.asarray(label_in_topk(logits, labels, k)), np.asarray(want)
    )
    got = topk_correct(logits, labels, k)
    assert got.shape == () and got.dtype == jnp.float32
    assert float(got) == float(want.mean())


def test_topk_correct_orders_specials_as_lax_top_k():
    """NaN, the infinities and -0.0 against 0.0 rank as in ``top_k``."""
    nan, inf = float("nan"), float("inf")
    rows = jnp.array([[nan, 1.0, 2.0, nan], [inf, inf, -inf, 0.0],
                      [-inf] * 4, [-nan, -0.0, 0.0, -0.0]])
    for label in range(4):
        labels = jnp.full((4,), label, jnp.int32)
        for k in (1, 2, 3):
            _, top = jax.lax.top_k(rows, k)
            np.testing.assert_array_equal(
                np.asarray(label_in_topk(rows, labels, k)),
                np.asarray((top == label).any(-1)),
            )


def test_train_metrics_never_sort():
    """``lax.top_k`` lowers to a full sort of the class axis on the TPU: 83%
    of the LM step at 50,000 classes (PERF.md, PR 27).  Neither metrics
    function of the train path may bring it back."""
    logits = jnp.zeros((2, 6, 13), jnp.bfloat16)
    tokens = jnp.zeros((2, 6), jnp.int32)

    def lm_metrics(logits, tokens):  # as workloads/transformer.lm_metrics
        return topk_correct(logits[:, :-1], tokens[:, 1:], 1)

    def sorts(fn, *args):
        return {"top_k", "sort"} & set(
            primitive_counts(jax.make_jaxpr(fn)(*args).jaxpr)
        )

    # the walk sees inside a nested jit, under the names the guard forbids
    nested = jax.jit(lambda x: (jax.lax.top_k(x, 1)[1], jnp.sort(x)))
    assert sorts(lambda x: nested(x), logits) == {"top_k", "sort"}
    assert not sorts(lm_metrics, logits, tokens)
    assert not sorts(
        classification_metrics, logits[:, 0], tokens[:, 0], jnp.float32(0.0)
    )


def _lm_step(devices, fsdp, tokens):
    """One step of a tiny LM under the transformer workload's fsdp rules
    (the head's vocabulary axis sharded over ``fsdp``); returns its metrics."""
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward,
        init_params,
        next_token_loss,
    )
    from distributeddeeplearning_tpu.train.state import TrainState

    mesh = create_mesh(MeshSpec(fsdp=fsdp), devices=devices)
    params = init_params(
        jax.random.key(5), num_layers=2, d_model=32, num_heads=2, d_ff=64,
        vocab_size=64, max_len=tokens.shape[1],
    )
    # a head far from its 0.02 init: logits spread, so top1/top5 are not ~0
    params["head"] = params["embed"].T * 40.0

    def apply_fn(variables, tokens, train=True, mutable=None, rngs=None):
        out = forward(variables["params"], tokens, num_heads=2)
        return (out, {}) if mutable is not None else out

    tx = optax.sgd(0.1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params), batch_stats={}, apply_fn=apply_fn, tx=tx,
    )
    rules = [("layers", "pipe"), ("vocab", "fsdp"), ("width", "fsdp")]
    logical_axes = {
        "embed": ("vocab", None), "pos": None, "head": (None, "vocab"),
        "blocks": {
            "qkv": ("layers", None, "width"), "proj": ("layers", "width", None),
            "w_in": ("layers", None, "width"), "w_out": ("layers", "width", None),
            "ln1": ("layers", None), "ln2": ("layers", None),
        },
    }

    def lm_loss(logits, labels, *, label_smoothing=0.0):
        return next_token_loss(logits, labels)

    def lm_metrics(logits, tokens, loss):
        return {"loss": loss,
                "top1": topk_correct(logits[:, :-1], tokens[:, 1:], 1),
                "top5": topk_correct(logits[:, :-1], tokens[:, 1:], 5)}

    step = build_train_step(
        mesh, state, compute_dtype=jnp.float32, rules=rules,
        logical_axes=logical_axes, loss_fn=lm_loss, metrics_fn=lm_metrics,
    )
    state = place_state(mesh, state, rules=rules, logical_axes=logical_axes)
    if fsdp > 1:
        assert "fsdp" in state.params["head"].sharding.spec[1:]
    _, metrics = step(state, shard_batch(mesh, {"input": tokens, "label": tokens}))
    return {name: float(value) for name, value in metrics.items()}


def test_topk_with_sharded_vocab_equals_single_device():
    """The label's logit is picked and the classes ahead of it are counted
    across the shards of the vocabulary axis: same top1/top5 as one device."""
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    tokens[:, 1::2] = tokens[:, 0:-1:2]  # a copy task: some predictions hit
    sharded = _lm_step(jax.devices(), 4, tokens)
    single = _lm_step(jax.devices()[:1], 1, tokens)
    assert 0.0 < single["top1"] < single["top5"] < 1.0
    assert sharded["top1"] == single["top1"]
    assert sharded["top5"] == single["top5"]
    np.testing.assert_allclose(sharded["loss"], single["loss"], rtol=1e-5)


def test_bert_with_dropout_trains(mesh8):
    """Dropout RNG plumbing: the default BERT config (dropout 0.1) must train."""
    from distributeddeeplearning_tpu.models import get_model as gm

    model = gm(
        "bert-base", num_layers=1, hidden_size=32, num_heads=2,
        intermediate_size=64, vocab_size=50, num_classes=3,
        max_position_embeddings=16, dtype=jnp.float32,  # dropout_rate=0.1 default
    )
    tx = sgd_momentum(optax.constant_schedule(0.01))
    state = create_train_state(
        jax.random.key(0), model, (2, 8), tx, input_dtype=jnp.int32
    )
    step = build_train_step(mesh8, state, compute_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    batch = shard_batch(
        mesh8,
        {
            "input": rng.integers(0, 50, (16, 8)).astype(np.int32),
            "label": rng.integers(0, 3, (16,)).astype(np.int32),
        },
    )
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_fsdp_opt_state_mirrors_param_sharding():
    """ZeRO contract: momentum buffers shard exactly like their params."""
    from distributeddeeplearning_tpu.models import get_model as gm
    from distributeddeeplearning_tpu.parallel.sharding import (
        RULES_FSDP,
        model_logical_axes,
    )

    mesh = create_mesh(MeshSpec(fsdp=8))
    model = gm(
        "bert-base", num_layers=1, hidden_size=32, num_heads=2,
        intermediate_size=64, vocab_size=50, num_classes=3,
        max_position_embeddings=16, dropout_rate=0.0, dtype=jnp.float32,
    )
    axes = model_logical_axes(
        model, jax.random.key(0), np.zeros((2, 8), np.int32), train=False
    )
    tx = sgd_momentum(optax.constant_schedule(0.01))
    state = create_train_state(
        jax.random.key(0), model, (2, 8), tx, input_dtype=jnp.int32
    )
    step = build_train_step(
        mesh, state, compute_dtype=jnp.float32,
        rules=RULES_FSDP, logical_axes=axes,
    )
    rng = np.random.default_rng(0)
    batch = shard_batch(
        mesh,
        {
            "input": rng.integers(0, 50, (16, 8)).astype(np.int32),
            "label": rng.integers(0, 3, (16,)).astype(np.int32),
        },
    )
    state, _ = step(state, batch)
    kernel = state.params["layer0"]["mlp_in"]["kernel"]
    assert "fsdp" in tuple(kernel.sharding.spec)
    # momentum trace for the same param must carry the same sharding
    momentum_leaves = [
        leaf
        for sub in jax.tree_util.tree_leaves(
            state.opt_state, is_leaf=lambda x: hasattr(x, "sharding")
        )
        if hasattr(sub, "sharding")
        for leaf in [sub]
        if leaf.shape == kernel.shape
    ]
    assert momentum_leaves
    assert any(
        leaf.sharding.is_equivalent_to(kernel.sharding, leaf.ndim)
        for leaf in momentum_leaves
    )


def test_label_smoothing_changes_loss(mesh8):
    # The state fed to a step must share the model/tx objects of the
    # state_example the step was built from (static pytree fields).
    model = get_model("resnet18", num_classes=NCLS, dtype=jnp.float32)
    tx = sgd_momentum(optax.constant_schedule(0.1))

    def mk():
        return create_train_state(jax.random.key(0), model, (8, *IMG), tx)

    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    plain = build_train_step(mesh8, mk(), compute_dtype=jnp.float32)
    smooth = build_train_step(
        mesh8, mk(), compute_dtype=jnp.float32, label_smoothing=0.1
    )
    _, m_plain = plain(mk(), batch)
    _, m_smooth = smooth(mk(), batch)
    assert float(m_plain["loss"]) != float(m_smooth["loss"])


def _bert_state_and_model(seed=0):
    model = get_model(
        "bert-base", num_layers=2, hidden_size=32, num_heads=2,
        intermediate_size=64, vocab_size=50, num_classes=3,
        max_position_embeddings=16, dropout_rate=0.0, dtype=jnp.float32,
    )
    tx = sgd_momentum(optax.constant_schedule(0.05))
    state = create_train_state(
        jax.random.key(seed), model, (2, 8), tx, input_dtype=jnp.int32
    )
    return state, model, tx


def test_grad_accumulation_matches_full_batch(mesh8):
    """accum_steps=4 on the same global batch computes the SAME update as
    one full-batch step (stat-free model; VERDICT r02 item 5 contract)."""
    rng = np.random.default_rng(7)
    batch_np = {
        "input": rng.integers(0, 50, (32, 8)).astype(np.int32),
        "label": rng.integers(0, 3, (32,)).astype(np.int32),
    }
    batch = shard_batch(mesh8, batch_np)

    state_a, _, _ = _bert_state_and_model()
    step_a = build_train_step(mesh8, state_a, compute_dtype=jnp.float32)
    state_a, m_a = step_a(state_a, batch)

    state_b, _, _ = _bert_state_and_model()
    step_b = build_train_step(
        mesh8, state_b, compute_dtype=jnp.float32, accum_steps=4
    )
    state_b, m_b = step_b(state_b, batch)

    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        state_a.params,
        state_b.params,
    )


def test_grad_accumulation_batchnorm_model_trains(mesh8):
    """BN models train under accumulation (sequential EMA stats updates)."""
    state = _make_state()
    step = build_train_step(
        mesh8, state, compute_dtype=jnp.float32, accum_steps=2
    )
    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    state, first = step(state, batch)
    for _ in range(4):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < float(first["loss"])
    assert int(state.step) == 5  # one optimizer update per step call


def test_grad_accumulation_rejects_indivisible_batch(mesh8):
    state = _make_state()
    step = build_train_step(
        mesh8, state, compute_dtype=jnp.float32, accum_steps=3
    )
    batch = shard_batch(mesh8, synthetic_batch(16, IMG, NCLS))
    with pytest.raises(ValueError, match="not divisible"):
        step(state, batch)
