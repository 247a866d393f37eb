"""Drives a train cell: the step that the program's `build_train_step`
returns, built by the configuration's family (`families/<family>.py`:
`build_train`) the way the workload's `main` builds it, in the harness's own
loop (not `Trainer.fit`, which cannot be stopped after N seconds
without a change to the program).

Set-up builds ONE object, the compiled step with its state, drives it from the
seed through its first three steps (kept for the comparison with the plain
reference), and hands the same object to the window.
"""
import time

import numpy as np

import harness
import traffic_gen

CHECK_STEPS = 3
#: what this driver and the train cells' whole-step reader ask of a family
NEEDS = ("make_params", "param_shapes", "build_train", "loss_and_grads",
         "split_layers", "train_token_flops", "PROGRAMS")


def _schedule_lr(job, count):
    """The workload's warm-up-then-linear-decay schedule, in plain Python."""
    warm = max(int(job["total_steps"] * job["warmup_fraction"]), 1)
    if count < warm:
        return job["base_lr"] * count / warm
    rest = max(job["total_steps"] - warm, 1)
    return job["base_lr"] * max(1.0 - (count - warm) / rest, 0.0)


def aot_compile(cfg, family, job, devices):
    """Compile the step for described devices (tools/aot_compile.py)."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.parallel.sharding import batch_sharding

    params = family.param_shapes(cfg)
    mesh, step, state = family.build_train(cfg, job, devices, params)
    rows = job["rows_per_chip"] * len(devices)
    toks = jax.ShapeDtypeStruct((rows, job["seq_len"]), jnp.int32,
                                sharding=batch_sharding(mesh))
    return step.lower(state, {"input": toks, "label": toks}).compile()


def _adam_state(opt_state):
    import jax

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu") and hasattr(n, "nu"))
        if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state in the optimizer state")
    return found[0]


def run(*, manifest, cell, cfg, family, mix, limits, args, devices,
        t_process_start):
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.parallel import shard_batch

    job = mix
    compiles = harness.CompileCounter()
    seconds = float(args.seconds)
    vocab = cfg["vocab_size"]
    rows = job["rows_per_chip"] * len(devices)
    tokens_per_step = rows * job["seq_len"]

    phases = {"imports_s": time.perf_counter() - t_process_start}
    params = jax.block_until_ready(family.make_params(args.seed, cfg))
    phases["weights_s"] = time.perf_counter() - t_process_start
    mesh, step, state = family.build_train(cfg, job, devices, params)
    del params  # the step donates its state: the reference makes its own copy

    def feed(k):
        toks = traffic_gen.train_batch(job, vocab_size=vocab, seed=args.seed,
                                       step=k, rows=rows)
        return shard_batch(mesh, {"input": toks, "label": toks})

    # -- the first three steps: the window's own call and feed ------------------
    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    losses, kept = [], {}
    for k in range(CHECK_STEPS):
        state, metrics = step(state, feed(k))
        losses.append(metrics["loss"])
        if k == 0:
            kept["mu1"] = copy(_adam_state(state.opt_state).mu)
    kept["params3"] = copy(state.params)
    losses = [float(x) for x in losses]
    jax.block_until_ready(kept)
    phases["first_steps_s"] = time.perf_counter() - t_process_start

    tracer = harness.TraceWindow(bool(args.trace), *[
        f * seconds for f in job["trace_window_share"]])
    compiles_before = compiles.count
    t0 = time.perf_counter()
    setup_s = t0 - t_process_start

    # -- the window: the same object goes on from step 4 ------------------------
    done_steps, k, pending = 0, CHECK_STEPS, None
    with harness.mark("train loop"):
        while True:
            now = time.perf_counter() - t0
            tracer.tick(now)
            if now >= seconds:
                break
            with harness.mark("feed+dispatch"):
                state, metrics = step(state, feed(k))
            k += 1
            if pending is not None:
                with harness.mark("wait previous step"):
                    pending.block_until_ready()
                done_steps += 1
            pending = metrics["loss"]
        if pending is not None:
            pending.block_until_ready()
            done_steps += 1
    window_s = time.perf_counter() - t0
    tracer.stop()
    compiles_in_window = compiles.count - compiles_before
    last_loss = float(pending) if pending is not None else None
    device = harness.device_block(devices)

    numbers = {
        "train_tokens_per_s": done_steps * tokens_per_step / window_s,
        "setup_s": setup_s,
    }
    events = tracer.events()
    ctx = harness.context(
        cell=cell, cfg=cfg, family=family, mix=job, chips=len(devices),
        seconds=seconds, device_kind=device["kind"], events=events,
        enclosing_mark="bench/train loop", tracer=tracer, t0=t0,
        rows=rows, tokens_per_step=tokens_per_step, done_steps=done_steps,
        window_s=window_s,
    )
    result = {"correct": None, "attempted": done_steps, "failed": 0,
              "metrics": {}, "device": device}
    harness.fill_metrics(result, manifest, cell, numbers, ctx, bool(args.trace))

    # -- correct: the first three steps against the plain reference -------------
    del state, metrics, pending
    checks = {
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "last_loss_finite": {"value": 0.0 if last_loss is not None and
                             np.isfinite(last_loss) else 1.0, "limit": 0},
    }
    compared, stand_ins = _compare(cfg, family, job, limits, args.seed, rows,
                                   losses, kept, bool(args.control))
    checks.update(compared)
    result["correct"] = harness.judge(checks)
    harness.judge_stand_ins(result, checks, stand_ins)
    result["window"] = {"seconds": seconds, "window_s": window_s,
                        "steps": done_steps, "step_ms": 1e3 * window_s / max(done_steps, 1),
                        "losses": losses, "last_loss": last_loss,
                        "setup_reached_s": phases}
    result["checks"] = checks
    return result


def _norms(family, tree):
    import jax.numpy as jnp

    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in family.split_layers(tree).items()}


def worst_leaf_gap(prog: dict, ref: dict, leave_out=()):
    """The widest gap between the program's norm and the reference's over the
    leaves, against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    names = [n for n in ref if n not in leave_out]
    median = float(np.median([ref[n] for n in names]))
    worst, at = 0.0, None
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30)
        if gap > worst:
            worst, at = gap, n
    return worst, at


def reference_steps(cfg, family, job, seed, rows, precision="float32",
                    rows_used=None):
    """Three steps of the family's plain reference from the seed: (losses,
    clipped first gradient, parameters' change after the three). `rows_used` plants
    the fault "half of the batch left out, the mean taken over the rest"."""
    import jax
    import jax.numpy as jnp

    import reference

    vocab = cfg["vocab_size"]
    params0 = family.make_params(seed, cfg)
    params = params0
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for k in range(CHECK_STEPS):
        toks = traffic_gen.train_batch(job, vocab_size=vocab, seed=seed, step=k,
                                       rows=rows)
        if rows_used is not None:
            toks = toks[:rows_used]
        loss, grads = family.loss_and_grads(
            params, jnp.asarray(toks), cfg, precision=precision)
        params, mu, nu, clipped = reference.adamw_step(
            params, mu, nu, grads, k, _schedule_lr(job, k),
            b1=0.9, b2=0.999, eps=1e-6, weight_decay=job["weight_decay"],
            clip=job["grad_clip_norm"])
        losses.append(float(loss))
        if k == 0:
            first_grad = clipped
    change = jax.tree_util.tree_map(jnp.subtract, params, params0)
    return losses, _norms(family, first_grad), _norms(family, change)


def gaps(prog, ref):
    """The numbers compared, program (or what is put in its place) against
    the reference: each (losses, first-gradient norms, change norms)."""
    p_losses, p_grad, p_change = prog
    r_losses, r_grad, r_change = ref
    median_grad = float(np.median(list(r_grad.values())))
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: left out of the change by a rule on the
    # reference's gradient, under a thousandth of the median leaf's
    still = [n for n, g in r_grad.items() if g < 1e-3 * median_grad]
    out = {f"loss_gap_step{k + 1}": abs(p - r) / abs(r)
           for k, (p, r) in enumerate(zip(p_losses, r_losses))}
    out["grad_norm_gap"], out["grad_norm_gap_at"] = worst_leaf_gap(p_grad, r_grad)
    out["change_norm_gap"], out["change_norm_gap_at"] = worst_leaf_gap(
        p_change, r_change, leave_out=still)
    return out


def _compare(cfg, family, job, limits, seed, rows, losses, kept, control):
    import jax
    import jax.numpy as jnp

    b1 = 0.9
    grad1 = jax.tree_util.tree_map(lambda m: m / (1.0 - b1), kept["mu1"])
    params0 = family.make_params(seed, cfg)
    change = jax.tree_util.tree_map(jnp.subtract, kept["params3"], params0)
    prog = (losses, _norms(family, grad1), _norms(family, change))
    del grad1, change, params0
    kept.clear()
    ref = reference_steps(cfg, family, job, seed, rows)
    out = {}
    numbers = gaps(prog, ref)
    for name, value in numbers.items():
        if name.endswith("_at"):
            continue
        out[name] = {"value": value, "limit": limits.get(name)}
    out["worst_leaves"] = {"value": None, "limit": None,
                           "grad": numbers["grad_norm_gap_at"],
                           "change": numbers["change_norm_gap_at"]}
    stand_ins = {}
    if control:
        # the reference put in the program's place: in the precision below the
        # job's, and with half of the batch left out
        for label, kw in (("control", {"precision": limits["control_precision"]}),
                          ("halfbatch", {"rows_used": rows // 2})):
            stood = gaps(reference_steps(cfg, family, job, seed, rows, **kw),
                         ref)
            stand_ins[label] = {name: value for name, value in stood.items()
                                if not name.endswith("_at")}
    return out, stand_ins
