"""The `opt` family: the OPT/Galactica decoder block as this repo's program
runs it (`models/pipelined_transformer.py`).

A configuration's file names its `family`; `harness.load_family` loads
`benchmarks/families/<family>.py` by that name, and the drivers, the readers
of the whole-step shares and `tools/aot_compile.py` reach everything that
depends on the model's architecture through it and through nothing else. A
configuration of another architecture arrives as new files: a module like
this one, the files it imports beside it in this directory (its weights, its
plain reference, its counts), a configuration, a limits file and entries in
`BENCHMARK.json`. This module binds what `weights.py`, `reference.py` and
`flops.py` already hold; it is the contract written out.

What a family gives (a family that is only served gives no `build_train`,
`loss_and_grads`, `split_layers` or `train_token_flops`; one that is only
trained no `build_serve`, `served_token_gaps` or `serve_token_flops`; a cell
whose driver asks for a name that is missing stops before any device work):

- `make_params(seed, cfg)`, `param_shapes(cfg, sharding=None)`: the seeded
  weights, made on the device in one jitted call, in the storage the
  configuration states; the same pytree as shapes.
- `build_serve(cfg, params)` -> `(engine, scheduler)`: the program's own
  engine and `ContinuousBatchingScheduler`, built as `ddlt serve` builds them.
- `build_train(cfg, job, devices, params)` -> `(mesh, step, state)`: the step
  the program's `build_train_step` returns, built as the workload's `main`
  builds it; `params` may be shapes (then nothing is placed).
- `served_token_gaps(params, tokens, cfg, precision)`, `loss_and_grads(params,
  batch, cfg, precision)`: the family's plain reference, which imports
  nothing of the program. `split_layers(tree)`: name -> array, the leaves the
  train comparison takes its norms over (stacked layers split).
- `matmul_params(cfg)`, `serve_token_flops(cfg, context)`,
  `train_token_flops(cfg, seq_len)`: the work one token needs, from shapes.
- `PROGRAMS`: the names of the family's programs in the profiler's trace.
- `aot_serve_programs(cfg, kv_pages, sharding)` (only `tools/aot_compile.py`
  asks): name -> (function, abstract arguments) of the served programs.

What the serve driver asks of the engine and the scheduler, and so what "the
normal path" means to the harness. An engine of another family has to give:

- `engine.prefill_chunk`, `engine.page_size`: the widths prompts are cut into
  and the prefix cache shares at; `engine.chunk_shapes(n)`: the set of chunk
  widths a prompt of `n` tokens compiles, so that the warm-up covers them;
- `engine.reset_stats()`, then `engine.prefix_hit_tokens` and
  `engine.prompt_tokens_seen` counted from there;
- `engine.decode_impl`, `engine.kv_dtype`: printed in the result's `window`;
- `scheduler.run(requests, poll=, on_token=, on_complete=, should_drain=)`
  over the program's `Request(uid, prompt, max_new_tokens)`, returning
  `(results, ServeReport)`; results carry `uid`, `tokens`, `finish_reason`,
  `queue_wait_s`; the report is what the counter readers read.
"""
import flops
import reference
import weights

#: the family's programs as the `XLA Modules` line of a trace names them
PROGRAMS = {
    "decode": "jit__decode_fn",
    "prefill_chunk": "jit__chunk_fn",
    "train_step": "jit_step_fn",
}

make_params = weights.make_params
param_shapes = weights.param_shapes
matmul_params = flops.matmul_params
serve_token_flops = flops.serve_token_flops
train_token_flops = flops.train_token_flops


def build_serve(cfg, params):
    from distributeddeeplearning_tpu.serve.engine import PagedInferenceEngine
    from distributeddeeplearning_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    geo = cfg["serving"]
    engine = PagedInferenceEngine(
        params,
        num_heads=cfg["num_attention_heads"],
        batch_slots=geo["batch_slots"],
        max_seq=geo["max_seq"],
        page_size=geo["page_size"],
        num_pages=geo["kv_pages"],
        prefill_chunk=geo["prefill_chunk"],
        decode_kernel=geo["decode_kernel"],
        prefix_cache=geo["prefix_cache"],
    )
    return engine, ContinuousBatchingScheduler(engine, eos_id=None)


def build_train(cfg, job, devices, params):
    """(mesh, step, state): the program's train step over `devices`."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward, next_token_loss)
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.train.schedule import (
        warmup_linear_decay_schedule)
    from distributeddeeplearning_tpu.train.state import TrainState, adamw
    from distributeddeeplearning_tpu.train.step import (
        build_train_step, place_state, topk_correct)

    heads = cfg["num_attention_heads"]
    fsdp = job.get("fsdp", 1)
    mesh = create_mesh(MeshSpec(fsdp=fsdp), devices=devices)
    dtype = jnp.bfloat16 if job["compute_dtype"] == "bfloat16" else jnp.float32
    attention, attention_fn = job["attention"], None
    if attention == "flash" and mesh.devices.size > 1:
        from distributeddeeplearning_tpu.ops import make_flash_attention

        attention_fn = make_flash_attention(mesh=mesh, causal=True)
    remat = bool(job.get("remat", False))

    def apply_fn(variables, tokens, train=True, mutable=None, rngs=None):
        p = jax.tree_util.tree_map(
            lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
            else a, variables["params"])
        out = forward(p, tokens, num_heads=heads, attention=attention,
                      attention_fn=attention_fn, remat=remat).astype(jnp.float32)
        return (out, {}) if mutable is not None else out

    schedule = warmup_linear_decay_schedule(
        job["base_lr"], job["total_steps"], warmup_fraction=job["warmup_fraction"])
    tx = adamw(schedule, weight_decay=job["weight_decay"],
               grad_clip_norm=job["grad_clip_norm"])
    abstract = not isinstance(jax.tree_util.tree_leaves(params)[0], jax.Array)
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32) if abstract
        else jnp.zeros((), jnp.int32),
        params=params,
        opt_state=jax.eval_shape(tx.init, params) if abstract else tx.init(params),
        batch_stats={}, apply_fn=apply_fn, tx=tx,
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
        return {"loss": loss.astype(jnp.float32),
                "top1": topk_correct(logits[:, :-1], tokens[:, 1:], 1),
                "perplexity": jnp.exp(loss).astype(jnp.float32)}

    step = build_train_step(
        mesh, state, schedule=schedule, compute_dtype=dtype, rules=rules,
        logical_axes=logical_axes, loss_fn=lm_loss, metrics_fn=lm_metrics,
        rng=jax.random.key(1),
    )
    if not abstract:
        state = place_state(mesh, state, rules=rules, logical_axes=logical_axes)
    return mesh, step, state


def served_token_gaps(params, tokens, cfg, precision="float32"):
    return reference.served_token_gaps(
        params, tokens, num_heads=cfg["num_attention_heads"], precision=precision)


def loss_and_grads(params, batch, cfg, precision="float32"):
    return reference.loss_and_grads(
        params, batch, num_heads=cfg["num_attention_heads"], precision=precision)


def split_layers(tree):
    """name -> array, the stacked block leaves split per layer."""
    out = {}
    for name in ("embed", "pos", "head"):
        out[name] = tree[name]
    for name, leaf in tree["blocks"].items():
        for layer in range(leaf.shape[0]):
            out[f"blocks.{name}.{layer}"] = leaf[layer]
    return out


def aot_serve_programs(cfg, kv_pages, sharding):
    """The paged decode step and one prefill chunk over a pool of `kv_pages`,
    as functions with the shapes to lower them at (the pool is argument 1 and
    is donated), and the pool's logical bytes."""
    import math

    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward_decode_paged, forward_prefill_chunk)

    geo = cfg["serving"]
    params = param_shapes(cfg, sharding)
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    ps, slots = geo["page_size"], geo["batch_slots"]
    nb = -(-geo["max_seq"] // ps)
    pool = (kv_pages + 1, cfg["num_hidden_layers"], ps, h, hd)
    cache = {k: jax.ShapeDtypeStruct(pool, jnp.float32, sharding=sharding)
             for k in "kv"}
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)  # noqa: E731

    def decode(p, c, tok, pos, tables):
        logits, c = forward_decode_paged(p, tok, c, pos, tables, num_heads=h,
                                         page_size=ps, kernel="pallas")
        return jnp.argmax(logits, -1), jnp.isfinite(logits).all(-1), c

    def chunk(p, c, toks, table, off):
        return forward_prefill_chunk(p, toks, c, table, off, num_heads=h,
                                     page_size=ps, kernel="pallas")

    programs = {
        "decode": (decode, (params, cache, i32(slots), i32(slots), i32(slots, nb))),
        "prefill_chunk": (chunk, (params, cache, i32(1, geo["prefill_chunk"]),
                                  i32(nb), i32())),
    }
    return programs, 2 * 4 * math.prod(pool)
