"""Jitted train/eval step builders — the heart of the DP runtime.

The reference's hot loop is `forward → loss → backward → per-gradient Horovod
allreduce (NCCL) → optimizer.step` driven from Python per batch
(``imagenet_pytorch_horovod.py:166-200``; TF Estimator equivalent
``resnet_main.py:282-284``).  TPU-native, the whole thing is ONE compiled XLA
program: the batch arrives sharded over the mesh's data axes, the gradient
all-reduce is inserted by XLA from sharding propagation (riding ICI, no
NCCL/MPI), and metrics reduce in the same program — zero host round-trips
per step beyond feeding data.

Step contract:
    train_step(state, batch) -> (new_state, metrics)   [state donated]
    eval_step(state, batch)  -> metrics
with ``batch = {"image"|"input": ..., "label": ...}`` sharded over (data,fsdp)
and metrics replicated fp32 scalars.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from distributeddeeplearning_tpu.obs.attrib import tracked_jit as _tracked_jit
from distributeddeeplearning_tpu.parallel.sharding import (
    batch_sharding,
    param_shardings,
    replicated,
)
from distributeddeeplearning_tpu.utils.metrics import label_in_topk

PyTree = Any
Metrics = Dict[str, jax.Array]

COMM_DTYPES = {None: None, "f32": None, "float32": None,
               "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, *, label_smoothing: float = 0.0
) -> jax.Array:
    """Mean softmax cross-entropy with integer labels.

    Matches the reference's ``sparse_softmax_cross_entropy``
    (``resnet_main.py:96-101``) / ``nn.CrossEntropyLoss``
    (``imagenet_pytorch_horovod.py:180-182``).  Computed in fp32 regardless of
    the activation dtype.
    """
    logits = logits.astype(jnp.float32)
    if label_smoothing > 0.0:
        num_classes = logits.shape[-1]
        one_hot = optax.smooth_labels(
            jax.nn.one_hot(labels, num_classes), label_smoothing
        )
        return optax.softmax_cross_entropy(logits, one_hot).mean()
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def topk_correct(logits: jax.Array, labels: jax.Array, k: int) -> jax.Array:
    """Fraction of examples whose label is in the top-k logits — parity with
    ``accuracy(output, target, topk=(1,5))`` (``imagenet_pytorch_horovod.py:149-163``).
    ``logits`` [..., classes] against ``labels`` [...]: any leading dims."""
    return label_in_topk(logits, labels, k).mean()


def classification_metrics(logits: jax.Array, labels: jax.Array, loss: jax.Array) -> Metrics:
    return {
        "loss": loss.astype(jnp.float32),
        "top1": topk_correct(logits, labels, 1),
        "top5": topk_correct(logits, labels, 5),
    }


# Batch keys forwarded to the model as keyword inputs (transformer models
# take the padding mask alongside the token ids).
EXTRA_INPUT_KEYS = ("attention_mask", "token_type_ids")


def _cast_inputs(inputs: jax.Array, compute_dtype: jnp.dtype) -> jax.Array:
    """Cast float inputs to the compute dtype; integer inputs (token ids)
    pass through — bf16 cannot represent vocab-sized ids exactly."""
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        return inputs
    return inputs.astype(compute_dtype)


def _forward(state, params, inputs, train: bool, rngs=None, extras=None,
             batch_stats=None):
    """Apply the model, handling BN batch_stats models and stat-free models.

    Returns (logits, new_batch_stats, aux_loss) where ``aux_loss`` is the
    summed ``moe_losses`` collection (0.0 for models without MoE layers) —
    the Switch-style load-balance terms sown by ``models.moe.MoeMlp``.

    ``batch_stats`` overrides ``state.batch_stats`` so microbatched callers
    (gradient accumulation) can thread stats updated by earlier microbatches.
    """
    from distributeddeeplearning_tpu.models.moe import MOE_LOSS_COLLECTION

    stats = state.batch_stats if batch_stats is None else batch_stats
    has_stats = bool(jax.tree_util.tree_leaves(stats))
    variables = {"params": params}
    kwargs = dict(extras or {})
    if rngs:
        kwargs["rngs"] = rngs
    if has_stats:
        variables["batch_stats"] = stats
    if train:
        mutable = [MOE_LOSS_COLLECTION] + (["batch_stats"] if has_stats else [])
        logits, new_vars = state.apply_fn(
            variables, inputs, train=True, mutable=mutable, **kwargs
        )
        aux = sum(
            jnp.sum(leaf)
            for leaf in jax.tree_util.tree_leaves(
                new_vars.get(MOE_LOSS_COLLECTION, {})
            )
        )
        new_stats = new_vars.get("batch_stats", stats)
        return logits, new_stats, jnp.asarray(aux, jnp.float32)
    kwargs.pop("rngs", None)
    logits = state.apply_fn(variables, inputs, train=False, **kwargs)
    return logits, stats, jnp.zeros((), jnp.float32)


def _state_shardings(mesh, state_example, rules, logical_axes):
    """Sharding tree matching a TrainState.

    Params follow the logical-axis rules (replicated for pure DP); the
    optimizer state mirrors the param layout wherever optax keeps a
    params-shaped buffer (momentum/Adam moments) — without this, FSDP/TP
    models would replicate fp32 optimizer moments on every chip, forfeiting
    the memory the sharding exists to save.  Scalars (step counts) and
    batch_stats replicate.
    """
    r_shard = replicated(mesh)
    p_shard = param_shardings(mesh, state_example.params, rules, logical_axes)
    p_treedef = jax.tree_util.tree_structure(state_example.params)

    def params_like(subtree) -> bool:
        return jax.tree_util.tree_structure(subtree) == p_treedef

    def opt_leaf(subtree):
        # graft the full param-sharding tree over params-shaped subtrees
        return p_shard if params_like(subtree) else r_shard

    opt_example = state_example.opt_state
    if isinstance(opt_example, dict) and set(opt_example) == {"base", "residual"}:
        # comm-overlap layout (parallel/comms.py): per-bucket flat shards
        # (bare tuples of 1-D arrays) stay physically sharded over the
        # data axes — an eval step built from a prepared state must not
        # force-replicate the distributed optimizer buffers it never reads
        opt_shardings = _comm_opt_shardings(mesh, opt_example)
    else:
        opt_shardings = jax.tree_util.tree_map(
            opt_leaf, opt_example, is_leaf=params_like
        )
    return state_example.replace(
        step=r_shard,
        params=p_shard,
        opt_state=opt_shardings,
        batch_stats=jax.tree_util.tree_map(lambda _: r_shard, state_example.batch_stats),
    )


def place_state(mesh, state, *, rules=None, logical_axes=None):
    """Put a fresh ``TrainState`` onto the shardings the train step built
    from the same ``rules``/``logical_axes`` takes it in.  A workload with
    rule-sharded params (fsdp / tensor / pipe) calls this before
    ``Trainer.fit``: the placed state is the checkpoint RESTORE TEMPLATE,
    so a resume reads every leaf straight into its shards — restored into
    a fresh single-device template instead, the leaves come back committed
    to one device and the sharded step refuses them."""
    return jax.device_put(
        state, _state_shardings(mesh, state, rules or [], logical_axes)
    )


def _comm_opt_shardings(mesh, opt_state):
    """Shardings for a comm-overlap ``{"base", "residual"}`` opt_state:
    per-bucket flat vectors (the WUS optimizer shards and the compression
    residual) over the data axes, everything else replicated — the bucket
    spec comes out of the partition-rule layout table (``comm/`` rules),
    not a hand-wired PartitionSpec."""
    from distributeddeeplearning_tpu.parallel import sharding as _layout

    r = replicated(mesh)
    s = _layout.resolve_shardings(
        mesh, {"bucket": None}, prefix="comm"
    )["bucket"]

    def is_bucket_tuple(x):
        return (
            type(x) is tuple and len(x) > 0
            and all(getattr(e, "ndim", None) == 1 for e in x)
        )

    base = jax.tree_util.tree_map(
        lambda x: tuple(s for _ in x) if is_bucket_tuple(x) else r,
        opt_state["base"], is_leaf=is_bucket_tuple,
    )
    return {
        "base": base,
        "residual": tuple(s for _ in opt_state["residual"]),
    }


def build_train_step(
    mesh,
    state_example,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    label_smoothing: float = 0.0,
    schedule: Optional[optax.Schedule] = None,
    rules=None,
    logical_axes: Optional[PyTree] = None,
    loss_fn: Callable = cross_entropy_loss,
    metrics_fn: Callable = classification_metrics,
    rng: Optional[jax.Array] = None,
    moe_aux_weight: float = 0.01,  # Switch Transformer's α
    accum_steps: int = 1,
    input_transform: Optional[Callable] = None,
    skip_nonfinite: bool = False,
    comm_overlap: bool = False,
    bucket_mb: float = 4.0,
    comm_dtype: Optional[Any] = None,
    weight_update_sharding: bool = False,
    comm_skip: bool = False,
) -> Callable:
    """Compile the full DP training step over ``mesh``.

    Sharding layout: batch over the (data, fsdp) axes; params via
    ``param_shardings`` (replicated for pure DP — the Horovod contract — or
    rule-sharded for fsdp/tp models).  ``state_example`` supplies the pytree
    structure for sharding construction; the returned function is jitted with
    the state donated, so steady-state HBM holds one copy of params+opt state.

    ``rng`` seeds per-step stochastic layers (dropout); each step folds the
    step counter in, so resume at step k reproduces step k's dropout mask.

    ``input_transform`` runs on the inputs INSIDE the compiled step, before
    the compute-dtype cast — the hook for preprocessing that should ride the
    TPU instead of the host (e.g. ``raw_cache.uint8_normalizer()`` casting
    raw uint8 pixels and subtracting channel means; XLA fuses it into the
    first layer's input chain).

    ``accum_steps`` > 1 microbatches the step: the global batch is split into
    ``accum_steps`` equal slices along the batch axis and a ``lax.scan``
    accumulates the mean gradient before a SINGLE optimizer update — the
    global-batch lever when per-chip memory caps the resident batch (the
    reference's only lever was per-GPU batch × world size).  Activation
    memory scales with the microbatch; parameter/optimizer memory is
    unchanged.  For stat-free models the update is bitwise the same math as
    one big batch (mean of per-microbatch mean-grads == full-batch mean
    grad); BatchNorm models see ``accum_steps`` sequential EMA updates of
    batch statistics over microbatch moments instead of one global-batch
    moment — the standard, documented deviation.

    ``skip_nonfinite`` arms the in-program anomaly guard (the resilience
    layer's device half; ``train/resilience.py`` holds the host half): when
    the loss or the global gradient norm is non-finite, the parameter /
    optimizer / batch-stats update is **discarded inside the compiled step**
    (``step`` still advances, so step accounting and resume stay exact) and
    the metrics gain ``grad_norm`` plus an ``anomalous`` 0/1 flag the
    Trainer's ``AnomalyDetector`` consumes.  Off by default: the extra
    select is cheap but not free, and perf-critical runs should compile the
    identical program they always did.

    ``comm_overlap`` replaces the implicit post-backward GSPMD allreduce
    with the explicit schedule in ``parallel/comms.py``: gradients are
    flattened into fixed-size buckets (``bucket_mb``) and each bucket's
    reduce-scatter over the data axes is issued as soon as that
    microbatch's grads exist inside the accumulation scan — wire time
    overlaps the next microbatch's backward instead of serializing after
    it.  ``weight_update_sharding`` (ZeRO-style distributed optimizer for
    the replicated-params path) applies the optimizer to each chip's 1/N
    gradient shard only and all-gathers the updated params, cutting
    optimizer FLOPs and params-shaped optimizer HBM (momentum, Adam m/v)
    by the data-parallel degree; it assumes the optimizer transform is
    elementwise given (grads, state, params) — SGD/momentum/Adam qualify,
    ``optax.clip_by_global_norm`` does NOT (it would clip by the shard
    norm).  ``comm_dtype="bf16"`` halves wire bytes by compressing the
    reduce-scatter payload, with per-bucket f32 error-feedback residuals
    carried in the train state (and checkpointed) so the rounding error
    re-enters the next step's reduction instead of being lost.

    The comm_overlap path requires replicated params (pure DP — no
    ``rules``/``logical_axes``), and its returned step carries a
    ``prepare_state`` method that converts a fresh ``TrainState`` into the
    comm layout (flat-sharded optimizer buffers + residual slot) — call it
    once before the first step (and use the prepared state as the restore
    template).  ``comm_skip`` is a benchmarking-only debug knob that
    elides the collectives (numerics are garbage) so ``bench.py --comms``
    can price the compute-only step.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if comm_overlap:
        if rules or logical_axes is not None:
            raise ValueError(
                "comm_overlap is the explicit replicated-params (pure DP) "
                "schedule; FSDP/TP models keep the implicit GSPMD path "
                "(drop rules/logical_axes or comm_overlap)"
            )
        if comm_dtype not in COMM_DTYPES and comm_dtype is not jnp.bfloat16:
            raise ValueError(
                f"comm_dtype must be one of "
                f"{sorted(k for k in COMM_DTYPES if k)} or None, "
                f"got {comm_dtype!r}"
            )
        return _build_comm_overlap_step(
            mesh,
            state_example,
            compute_dtype=compute_dtype,
            label_smoothing=label_smoothing,
            schedule=schedule,
            loss_fn=loss_fn,
            metrics_fn=metrics_fn,
            rng=rng,
            moe_aux_weight=moe_aux_weight,
            accum_steps=accum_steps,
            input_transform=input_transform,
            skip_nonfinite=skip_nonfinite,
            bucket_mb=bucket_mb,
            comm_dtype=(
                jnp.bfloat16 if comm_dtype is jnp.bfloat16
                else COMM_DTYPES[comm_dtype]
            ),
            weight_update_sharding=weight_update_sharding,
            comm_skip=comm_skip,
        )
    if weight_update_sharding or comm_skip or comm_dtype not in (
        None, "f32", "float32"
    ):
        # silently dropping these would let an A/B run believe it measured
        # the explicit schedule while compiling the implicit one
        raise ValueError(
            "weight_update_sharding/comm_skip/comm_dtype require "
            "comm_overlap=True"
        )
    b_shard = batch_sharding(mesh)
    r_shard = replicated(mesh)
    state_shardings = _state_shardings(mesh, state_example, rules or [], logical_axes)
    base_rng = rng if rng is not None else jax.random.key(0)

    def step_fn(state, batch):
        inputs = batch.get("image", batch.get("input"))
        if input_transform is not None:
            inputs = input_transform(inputs)
        labels = batch["label"]
        extras = {k: batch[k] for k in EXTRA_INPUT_KEYS if k in batch}
        step_rng = jax.random.fold_in(base_rng, state.step)

        def compute_loss(params, stats, mb_inputs, mb_labels, mb_extras, rngs):
            logits, new_stats, aux = _forward(
                state,
                params,
                _cast_inputs(mb_inputs, compute_dtype),
                train=True,
                rngs=rngs,
                extras=mb_extras,
                batch_stats=stats,
            )
            loss = loss_fn(logits, mb_labels, label_smoothing=label_smoothing)
            loss = loss + moe_aux_weight * aux
            return loss, (logits, new_stats)

        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

        def guarded_update(grads, new_stats, loss):
            """Apply the update only when loss and grad norm are finite;
            step advances either way (resume/step accounting stay exact)."""
            grad_norm = optax.global_norm(grads)
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            cand = state.apply_gradients(grads, batch_stats=new_stats)
            skipped = state.replace(step=cand.step)
            selected = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), cand, skipped
            )
            guard_metrics = {
                "grad_norm": grad_norm.astype(jnp.float32),
                "anomalous": (1.0 - ok.astype(jnp.float32)),
            }
            return selected, guard_metrics

        if accum_steps == 1:
            (loss, (logits, new_stats)), grads = grad_fn(
                state.params, state.batch_stats, inputs, labels, extras,
                {"dropout": step_rng},
            )
            guard_metrics = {}
            if skip_nonfinite:
                new_state, guard_metrics = guarded_update(
                    grads, new_stats, loss
                )
            else:
                new_state = state.apply_gradients(grads, batch_stats=new_stats)
            # Aux-head models (InceptionV3 aux_logits=True) return (main, aux);
            # metrics report on the main head only.
            main_logits = logits[0] if isinstance(logits, tuple) else logits
            metrics = metrics_fn(main_logits, labels, loss)
            metrics.update(guard_metrics)
        else:
            if inputs.shape[0] % accum_steps:
                raise ValueError(
                    f"global batch {inputs.shape[0]} not divisible by "
                    f"accum_steps={accum_steps}"
                )

            def split(x):
                # Interleaved split (row r -> microbatch r % accum_steps):
                # the batch axis is contiguously sharded over the data mesh
                # axes, so a contiguous [accum, B/accum] reshape would put
                # each microbatch on 1/accum of the devices and force a
                # resharding collective every scan iteration.  The strided
                # assignment keeps every microbatch spread over ALL devices
                # — each device scans over its own resident rows, zero data
                # movement — and the accumulated mean over the global batch
                # is identical either way.
                return x.reshape(
                    (x.shape[0] // accum_steps, accum_steps) + x.shape[1:]
                ).swapaxes(0, 1)

            micro = jax.tree_util.tree_map(
                split, {"inputs": inputs, "labels": labels, "extras": extras}
            )
            zero_grads = jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, dtype=jnp.float32), state.params
            )

            def body(carry, xs):
                grads_acc, stats, i = carry
                rngs = {"dropout": jax.random.fold_in(step_rng, i)}
                (loss, (logits, stats)), grads = grad_fn(
                    state.params, stats, xs["inputs"], xs["labels"],
                    xs["extras"], rngs,
                )
                grads_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
                )
                main_logits = logits[0] if isinstance(logits, tuple) else logits
                mb_metrics = metrics_fn(main_logits, xs["labels"], loss)
                return (grads_acc, stats, i + 1), mb_metrics

            (grads_sum, new_stats, _), metrics_stack = jax.lax.scan(
                body,
                (zero_grads, state.batch_stats, jnp.zeros((), jnp.int32)),
                micro,
            )
            inv = 1.0 / accum_steps
            grads = jax.tree_util.tree_map(
                lambda g, p: (g * inv).astype(p.dtype), grads_sum, state.params
            )
            metrics = jax.tree_util.tree_map(
                lambda m: m.mean(axis=0), metrics_stack
            )
            if skip_nonfinite:
                new_state, guard_metrics = guarded_update(
                    grads, new_stats, metrics["loss"]
                )
                metrics.update(guard_metrics)
            else:
                new_state = state.apply_gradients(grads, batch_stats=new_stats)
        if schedule is not None:
            metrics["lr"] = schedule(state.step).astype(jnp.float32)
        return new_state, metrics

    # attribution (obs/attrib.py): the train step's cost_analysis flops/
    # bytes are recorded at first compile and feed the MFU numerator,
    # the roofline denominator and the ATTRIB artifact
    return _tracked_jit("train.step.implicit", jax.jit(
        step_fn,
        in_shardings=(state_shardings, b_shard),
        out_shardings=(state_shardings, r_shard),
        donate_argnums=(0,),
    ))


class CommOverlapStep:
    """The compiled ``comm_overlap`` train step.

    Callable exactly like the plain jitted step (``step(state, batch)``,
    ``step.lower(...)``), plus the comm-layout plumbing callers need:
    ``prepare_state`` converts a fresh ``TrainState`` into the layout this
    step trains and checkpoints (flat-sharded optimizer buffers under
    weight-update sharding, the bf16 error-feedback residual slot), and
    ``wire_bytes()`` reports the analytic per-device bytes-on-wire model
    for the bench artifact.
    """

    def __init__(self, jitted, mesh, layout, *, comm_dtype,
                 weight_update_sharding, accum_steps):
        self._jitted = jitted
        self.mesh = mesh
        self.layout = layout
        self.comm_dtype = comm_dtype
        self.weight_update_sharding = weight_update_sharding
        self.accum_steps = accum_steps
        self.comm_overlap = True

    def __call__(self, state, batch):
        return self._jitted(state, batch)

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def prepare_state(self, state):
        from distributeddeeplearning_tpu.parallel import comms

        return comms.prepare_comm_state(
            self.mesh, state, self.layout,
            weight_update_sharding=self.weight_update_sharding,
            comm_dtype=self.comm_dtype,
        )

    def wire_bytes(self) -> Dict[str, int]:
        from distributeddeeplearning_tpu.parallel import comms

        return comms.ring_wire_bytes(
            self.layout, comm_dtype=self.comm_dtype,
            weight_update_sharding=self.weight_update_sharding,
            accum_steps=self.accum_steps,
        )


def _build_comm_overlap_step(
    mesh,
    state_example,
    *,
    compute_dtype,
    label_smoothing,
    schedule,
    loss_fn,
    metrics_fn,
    rng,
    moe_aux_weight,
    accum_steps,
    input_transform,
    skip_nonfinite,
    bucket_mb,
    comm_dtype,
    weight_update_sharding,
    comm_skip,
) -> CommOverlapStep:
    """The explicit-comms train step: shard_map over the data axes with
    bucketed reduce-scatter inside the accumulation scan, optional ZeRO
    weight-update sharding, optional bf16 wire compression with error
    feedback.  See ``build_train_step``'s docstring for semantics and
    ``parallel/comms.py`` for the collectives."""
    import types

    from jax import lax
    from jax.experimental.shard_map import shard_map

    from distributeddeeplearning_tpu.parallel import comms
    from distributeddeeplearning_tpu.parallel import sharding as _layout
    from distributeddeeplearning_tpu.parallel.mesh import (
        DATA_AXES,
        data_parallel_size,
    )

    n_shards = data_parallel_size(mesh)
    fsdp_size = mesh.shape["fsdp"]
    layout = comms.BucketLayout.for_tree(
        state_example.params,
        bucket_bytes=max(int(bucket_mb * 2**20), 4),
        shards=n_shards,
    )
    b_shard = batch_sharding(mesh)
    r_shard = replicated(mesh)
    shard_over_data = _layout.resolve_shardings(
        mesh, {"bucket": None}, prefix="comm"
    )["bucket"]
    p_treedef = jax.tree_util.tree_structure(state_example.params)
    base_rng = rng if rng is not None else jax.random.key(0)
    AX = DATA_AXES
    tx = state_example.tx
    apply_fn = state_example.apply_fn
    has_stats = bool(jax.tree_util.tree_leaves(state_example.batch_stats))
    # _forward only touches static attrs (apply_fn) when batch_stats is
    # passed explicitly; a namespace shim keeps the outer traced state out
    # of the shard_map body (its arrays enter as explicit arguments).
    fwd_shim = types.SimpleNamespace(apply_fn=apply_fn, batch_stats={})

    opt_shardings = comms.comm_opt_specs(
        state_example.opt_state, p_treedef, layout,
        weight_update_sharding=weight_update_sharding,
        spec_sharded=shard_over_data, spec_replicated=r_shard,
    )
    opt_specs = comms.comm_opt_specs(
        state_example.opt_state, p_treedef, layout,
        weight_update_sharding=weight_update_sharding,
        spec_sharded=_layout.data_spec(), spec_replicated=_layout.replicated_spec(),
    )
    n_buckets = layout.num_buckets
    residual_shardings = (
        tuple(shard_over_data for _ in range(n_buckets))
        if comm_dtype is not None else ()
    )
    residual_specs = (
        tuple(_layout.data_spec() for _ in range(n_buckets))
        if comm_dtype is not None else ()
    )
    state_shardings = state_example.replace(
        step=r_shard,
        params=jax.tree_util.tree_map(lambda _: r_shard, state_example.params),
        opt_state={"base": opt_shardings, "residual": residual_shardings},
        batch_stats=jax.tree_util.tree_map(
            lambda _: r_shard, state_example.batch_stats
        ),
    )

    def step_fn(state, batch):
        inputs = batch.get("image", batch.get("input"))
        if input_transform is not None:
            inputs = input_transform(inputs)
        labels = batch["label"]
        extras = {k: batch[k] for k in EXTRA_INPUT_KEYS if k in batch}
        if inputs.shape[0] % (n_shards * accum_steps):
            raise ValueError(
                f"global batch {inputs.shape[0]} not divisible by "
                f"data shards x accum_steps = {n_shards} x {accum_steps}"
            )
        step_rng = jax.random.fold_in(base_rng, state.step)
        parts = {"inputs": inputs, "labels": labels, "extras": extras}
        parts_spec = jax.tree_util.tree_map(
            lambda _: _layout.data_spec(), parts
        )

        def inner(params, opt_base, residuals, stats, key, data):
            dev = (
                lax.axis_index("data") * fsdp_size + lax.axis_index("fsdp")
            )

            def compute_loss(p, st, mb_inputs, mb_labels, mb_extras, rngs):
                logits, new_stats, aux = _forward(
                    fwd_shim, p, _cast_inputs(mb_inputs, compute_dtype),
                    train=True, rngs=rngs, extras=mb_extras, batch_stats=st,
                )
                loss = loss_fn(
                    logits, mb_labels, label_smoothing=label_smoothing
                )
                # Sown aux terms are global SUMS in the implicit path; the
                # local partial scales by the shard count so psum/N of the
                # gradients reproduces the same total.
                loss = loss + moe_aux_weight * aux * n_shards
                return loss, (logits, new_stats)

            grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

            def scatter(grads, res):
                buckets = layout.to_buckets(grads)
                if comm_skip:
                    return tuple(
                        layout.shard_slice(b, dev) for b in buckets
                    ), res
                if comm_dtype is None:
                    shards, _ = comms.reduce_scatter_buckets(buckets, AX)
                    return shards, res
                return comms.reduce_scatter_buckets(
                    buckets, AX, comm_dtype=comm_dtype, residuals=res,
                    shards=n_shards,
                )

            def gather(shards):
                if comm_skip:  # timing-only: numerics are garbage
                    return jnp.concatenate(
                        [jnp.tile(s, n_shards) for s in shards]
                    )
                return comms.gather_flat(shards, AX)

            if accum_steps == 1:
                # straight value_and_grad — no scan wrapper, no zero
                # accumulator (same minimal-program contract as the
                # implicit path's accum_steps == 1 special case)
                rngs = {"dropout": jax.random.fold_in(key, dev)}
                (loss, (logits, new_stats)), grads = grad_fn(
                    params, stats, data["inputs"], data["labels"],
                    data["extras"], rngs,
                )
                g_shards, new_residuals = scatter(grads, residuals)
                main_logits = logits[0] if isinstance(logits, tuple) else logits
                local_metrics = metrics_fn(main_logits, data["labels"], loss)
            else:
                def split(x):
                    # strided split of the LOCAL rows: local row l lands in
                    # microbatch l % accum — with the batch contiguously
                    # sharded over devices this reproduces the implicit
                    # path's global strided microbatches device-for-device
                    return x.reshape(
                        (x.shape[0] // accum_steps, accum_steps) + x.shape[1:]
                    ).swapaxes(0, 1)

                micro = jax.tree_util.tree_map(split, data)
                zero_shards = tuple(
                    jnp.zeros((n // n_shards,), jnp.float32)
                    for n in layout.bucket_sizes
                )

                def body(carry, xs):
                    acc, res, st, i = carry
                    rngs = {
                        "dropout": jax.random.fold_in(
                            jax.random.fold_in(key, i), dev
                        )
                    }
                    (loss, (logits, st)), grads = grad_fn(
                        params, st, xs["inputs"], xs["labels"], xs["extras"],
                        rngs,
                    )
                    # the reduce-scatter of THIS microbatch's buckets sits
                    # before the next iteration's backward in the dataflow:
                    # async collective start/done overlaps the wire with
                    # that compute, and the scan accumulates 1/N-sized
                    # scattered shards instead of full gradient trees
                    shards, res = scatter(grads, res)
                    acc = tuple(a + s for a, s in zip(acc, shards))
                    main_logits = (
                        logits[0] if isinstance(logits, tuple) else logits
                    )
                    mb_metrics = metrics_fn(main_logits, xs["labels"], loss)
                    return (acc, res, st, i + 1), mb_metrics

                (g_shards, new_residuals, new_stats, _), mstack = lax.scan(
                    body,
                    (zero_shards, residuals, stats, jnp.zeros((), jnp.int32)),
                    micro,
                )
                local_metrics = jax.tree_util.tree_map(
                    lambda m: m.mean(axis=0), mstack
                )

            # psum_scatter summed over N shards; the implicit path's grads
            # are the global-batch mean — one exact power-of-two rescale
            # (when N and accum are powers of two) recovers it.
            scale = 1.0 / (n_shards * accum_steps)
            g_shards = tuple(s * scale for s in g_shards)

            if weight_update_sharding:
                # ZeRO: this chip updates only its 1/N flat param shard
                # (optimizer buffers live as per-bucket flat shards in
                # opt_base), then all-gathers the updated params.
                p_buckets = layout.to_buckets(params)
                p_shards = tuple(
                    layout.shard_slice(b, dev) for b in p_buckets
                )
                updates, new_opt = tx.update(g_shards, opt_base, p_shards)
                new_p_shards = optax.apply_updates(p_shards, updates)
                new_params = layout.from_flat(gather(new_p_shards))
            else:
                grads_tree = layout.from_flat(gather(g_shards))
                updates, new_opt = tx.update(grads_tree, opt_base, params)
                new_params = optax.apply_updates(params, updates)

            # ONE tree-level collective for metrics (+ BatchNorm stats,
            # which under shard_map are per-device moments — averaged here,
            # the reference's per-GPU-BN semantics rather than GSPMD's
            # global-batch BN).
            payload = {"metrics": local_metrics}
            if has_stats:
                payload["stats"] = new_stats
            reduced = payload if comm_skip else lax.pmean(payload, AX)
            metrics = dict(reduced["metrics"])
            out_stats = reduced["stats"] if has_stats else new_stats

            if skip_nonfinite:
                sq = sum(
                    jnp.sum(jnp.square(s)).astype(jnp.float32)
                    for s in g_shards
                )
                grad_norm = jnp.sqrt(sq if comm_skip else lax.psum(sq, AX))
                ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(grad_norm)

                def keep(new, old):
                    return jax.tree_util.tree_map(
                        lambda a, b: jnp.where(ok, a, b), new, old
                    )

                new_params = keep(new_params, params)
                new_opt = keep(new_opt, opt_base)
                out_stats = keep(out_stats, stats)
                if comm_dtype is not None:
                    new_residuals = keep(new_residuals, residuals)
                metrics["grad_norm"] = grad_norm.astype(jnp.float32)
                metrics["anomalous"] = 1.0 - ok.astype(jnp.float32)

            return new_params, new_opt, new_residuals, out_stats, metrics

        inner_sm = shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                _layout.replicated_spec(), opt_specs, residual_specs,
                _layout.replicated_spec(), _layout.replicated_spec(),
                parts_spec,
            ),
            out_specs=(
                _layout.replicated_spec(), opt_specs, residual_specs,
                _layout.replicated_spec(), _layout.replicated_spec(),
            ),
            check_rep=False,
        )
        new_params, new_opt, new_res, new_stats, metrics = inner_sm(
            state.params, state.opt_state["base"], state.opt_state["residual"],
            state.batch_stats, step_rng, parts,
        )
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state={"base": new_opt, "residual": new_res},
            batch_stats=new_stats,
        )
        if schedule is not None:
            metrics["lr"] = schedule(state.step).astype(jnp.float32)
        return new_state, metrics

    jitted = _tracked_jit("train.step.comm_overlap", jax.jit(
        step_fn,
        in_shardings=(state_shardings, b_shard),
        out_shardings=(state_shardings, r_shard),
        donate_argnums=(0,),
    ))
    return CommOverlapStep(
        jitted, mesh, layout, comm_dtype=comm_dtype,
        weight_update_sharding=weight_update_sharding,
        accum_steps=accum_steps,
    )


def build_eval_step(
    mesh,
    state_example,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    rules=None,
    logical_axes: Optional[PyTree] = None,
    loss_fn: Callable = cross_entropy_loss,
    metrics_fn: Callable = classification_metrics,
    input_transform: Optional[Callable] = None,
) -> Callable:
    """Compile the eval step: forward + loss/top1/top5, no state mutation
    (parity with ``validate`` at ``imagenet_pytorch_horovod.py:203-230`` and
    rank-0 ``model.evaluate`` at ``resnet_main.py:293-307`` — except here
    every chip participates instead of eval running on rank 0 only)."""
    b_shard = batch_sharding(mesh)
    r_shard = replicated(mesh)
    state_shardings = _state_shardings(mesh, state_example, rules or [], logical_axes)

    def step_fn(state, batch):
        inputs = batch.get("image", batch.get("input"))
        if input_transform is not None:
            inputs = input_transform(inputs)
        labels = batch["label"]
        extras = {k: batch[k] for k in EXTRA_INPUT_KEYS if k in batch}
        logits, _, _ = _forward(
            state,
            state.params,
            _cast_inputs(inputs, compute_dtype),
            train=False,
            extras=extras,
        )
        loss = loss_fn(logits, labels)
        return metrics_fn(logits, labels, loss)

    return _tracked_jit("train.step.eval", jax.jit(
        step_fn,
        in_shardings=(state_shardings, b_shard),
        out_shardings=r_shard,
    ))
