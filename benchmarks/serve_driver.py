"""Drives a serve cell: an open loop of requests, each due at its own time,
through the scheduler's `run` over the engine that the configuration's family
builds (`families/<family>.py`: `build_serve`), in one process and one thread.

End-to-end numbers are the harness's own, by its own clock: a request's first
token is timed from when the request was DUE, tails are over all requests due
in the window, the rate is all tokens emitted inside the window over its
length.
"""
import time

import numpy as np

import harness
import traffic_gen


#: what this driver and the serve cells' whole-step readers ask of a family
NEEDS = ("make_params", "build_serve", "served_token_gaps", "matmul_params",
         "serve_token_flops", "PROGRAMS")


def _warm_up(engine, scheduler, schedule, mix, vocab):
    """Every shape this mix uses and no other: the prefill-chunk widths its
    prompts are cut into (with and without a hit on the shared prefix), the
    decode step and the sampler. The warm-up prompts carry the mix's shared
    prefix, so the window starts with the prefix pages resident, as in a
    server that has been up for a while."""
    from distributeddeeplearning_tpu.serve.scheduler import Request

    n_prefix = mix.get("shared_prefix_tokens", 0)
    prefix = list(schedule[0].prompt[:n_prefix])
    chunk, page = engine.prefill_chunk, engine.page_size
    needed = set()
    for item in schedule:
        n = len(item.prompt)
        hit = min(n_prefix, n - 1) // page * page
        needed |= engine.chunk_shapes(n) | engine.chunk_shapes(n - hit)
    rng = np.random.default_rng(0)
    requests = [
        Request(uid=f"warm{width}", max_new_tokens=4,
                prompt=prefix + rng.integers(
                    1, vocab, width if width == chunk else chunk + width).tolist())
        for width in sorted(needed, reverse=True)
    ]
    results, _ = scheduler.run(requests)
    bad = [r.uid for r in results if r.finish_reason != "length"]
    if bad:
        raise RuntimeError(f"warm-up requests did not finish: {bad}")


def run(*, manifest, cell, cfg, family, mix, limits, args, devices,
        t_process_start):
    import jax

    from distributeddeeplearning_tpu.serve.scheduler import Request

    compiles = harness.CompileCounter()
    phases = {"imports_s": time.perf_counter() - t_process_start}
    seconds = float(args.seconds)
    vocab = cfg["vocab_size"]
    schedule = traffic_gen.serve_schedule(mix, vocab_size=vocab, seed=args.seed,
                                          seconds=seconds)
    with jax.default_device(devices[0]):
        params = jax.block_until_ready(family.make_params(args.seed, cfg))
    phases["weights_s"] = time.perf_counter() - t_process_start
    engine, scheduler = family.build_serve(cfg, params)
    phases["engine_s"] = time.perf_counter() - t_process_start
    _warm_up(engine, scheduler, schedule, mix, vocab)
    phases["warm_up_s"] = time.perf_counter() - t_process_start
    engine.reset_stats()
    tracer = harness.TraceWindow(bool(args.trace), *[
        f * seconds for f in mix["trace_window_share"]])

    # -- the window ---------------------------------------------------------
    by_uid = {item.uid: item for item in schedule}
    released, token_times, done = {}, {uid: [] for uid in by_uid}, {}
    cursor = [0]
    drain_limit = float(mix["drain_limit_s"])
    compiles_before = compiles.count
    t0 = time.perf_counter()
    setup_s = t0 - t_process_start

    def poll():
        now = time.perf_counter() - t0
        tracer.tick(now)
        fresh = []
        while cursor[0] < len(schedule) and schedule[cursor[0]].due_s <= now:
            item = schedule[cursor[0]]
            cursor[0] += 1
            released[item.uid] = now
            fresh.append(Request(uid=item.uid, prompt=list(item.prompt),
                                 max_new_tokens=item.max_new_tokens))
        if cursor[0] >= len(schedule) and not fresh:
            return None  # every request has been handed over
        return fresh

    def on_token(uid, token):
        token_times[uid].append(time.perf_counter() - t0)

    def on_complete(result):
        done[result.uid] = result

    def past_drain_limit():
        return time.perf_counter() - t0 > seconds + drain_limit

    with harness.mark("scheduler.run"):
        _, report = scheduler.run(
            [], poll=poll, on_token=on_token, on_complete=on_complete,
            should_drain=past_drain_limit,
        )
    tracer.stop()
    t_end = time.perf_counter() - t0
    compiles_in_window = compiles.count - compiles_before
    device = harness.device_block(devices)

    # -- end-to-end numbers, all requests, the whole window -------------------
    finished = {uid: r for uid, r in done.items()
                if r.finish_reason in ("length", "eos")}
    failed = len(schedule) - len(finished)
    late = seconds + drain_limit
    ttft = [(token_times[i.uid][0] - i.due_s) if i.uid in finished else late
            for i in schedule]
    tpot = [(t[-1] - t[0]) / (len(t) - 1)
            for uid, t in token_times.items() if uid in finished and len(t) >= 2]
    in_window = sum(1 for t in token_times.values() for x in t if x <= seconds)
    numbers = {
        "ttft_p90_ms": 1e3 * traffic_gen.percentile(ttft, 90),
        "tpot_p90_ms": 1e3 * traffic_gen.percentile(tpot, 90) if tpot else None,
        "serve_tokens_per_s": in_window / seconds,
        "setup_s": setup_s,
    }

    # -- per-layer numbers (traced run) ---------------------------------------
    events = tracer.events()
    ctx = harness.context(
        cell=cell, cfg=cfg, family=family, mix=mix, chips=len(devices),
        seconds=seconds, device_kind=device["kind"], events=events,
        enclosing_mark="bench/scheduler.run", tracer=tracer, t0=t0,
        schedule=schedule, released=released, token_times=token_times,
        done=done, finished=finished, report=report, engine_stats={
            "prefix_hit_tokens": engine.prefix_hit_tokens,
            "prompt_tokens_seen": engine.prompt_tokens_seen,
        },
    )
    result = {
        "correct": None, "attempted": len(schedule), "failed": failed,
        "metrics": {}, "device": device,
    }
    harness.fill_metrics(result, manifest, cell, numbers, ctx, bool(args.trace))

    # -- correct: served tokens against the plain reference --------------------
    # after memory_peak_bytes was read and the program's state is freed
    engine_info = {"decode_impl": engine.decode_impl, "kv_dtype": engine.kv_dtype}
    del scheduler, engine
    checks = {
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    compared, stand_ins = _compare(cfg, family, mix, limits, params, finished,
                                   by_uid, args.seed, bool(args.control))
    checks.update(compared)
    result["correct"] = harness.judge(checks)
    harness.judge_stand_ins(result, checks, stand_ins)
    tails = {f"{name}_p{q}_ms": 1e3 * traffic_gen.percentile(values, q)
             for name, values in (("ttft", ttft), ("tpot", tpot)) if values
             for q in (50, 75, 90, 95, 100)}
    result["window"] = {"seconds": seconds, "ended_s": t_end, **tails,
                        "setup_reached_s": phases,
                        "requests_per_s": len(finished) / max(t_end, seconds),
                        "decode_steps": report.decode_steps, **engine_info}
    result["checks"] = checks
    return result


def _compare(cfg, family, mix, limits, params, finished, by_uid, seed, control):
    """A sample of the finished requests, drawn from the seed, with the
    longest in it: the family's reference runs once over each prompt with its
    served tokens; the number compared is the widest gap by which a served
    token's logit lies below the reference's best."""
    import jax.numpy as jnp

    if not finished:
        return {"token_gap_max": {"value": None,
                                  "limit": limits["token_gap_max"]}}, {}
    uids = sorted(finished)
    longest = max(uids, key=lambda u: len(by_uid[u].prompt) + len(finished[u].tokens))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    k = min(int(mix["check_sample_requests"]), len(uids))
    sample = [longest] + [u for u in rng.permutation(uids).tolist()
                          if u != longest][: k - 1]
    width = cfg["serving"]["max_seq"]
    gaps, low_gaps, stds, n_tokens = [], [], [], 0
    for uid in sample:
        prompt, served = list(by_uid[uid].prompt), list(finished[uid].tokens)
        seq = (prompt + served)[:width]
        tokens = np.zeros(width, np.int32)
        tokens[: len(seq)] = seq
        g, low, std = family.served_token_gaps(
            params, jnp.asarray(tokens), cfg,
            precision=limits["control_precision"] if control else "float32")
        lo, hi = len(prompt) - 1, len(seq) - 1  # positions that predict served tokens
        gaps.append(np.asarray(g)[lo:hi])
        low_gaps.append(np.asarray(low)[lo:hi])
        stds.append(np.asarray(std)[lo:hi])
        n_tokens += hi - lo
    gaps, low_gaps, stds = map(np.concatenate, (gaps, low_gaps, stds))
    out = {
        "token_gap_max": {"value": float(gaps.max()),
                          "limit": limits["token_gap_max"]},
        "token_gap_mean": {"value": float(gaps.mean()),
                           "limit": limits.get("token_gap_mean")},
        "tokens_compared": {"value": n_tokens, "at_least": True,
                            "limit": limits["tokens_compared_min"]},
        "logit_std": {"value": float(stds.mean()), "limit": None},
        "served_not_best_share": {"value": float((gaps > 0).mean()), "limit": None},
    }
    stand_ins = {}
    if control:
        # the control in the program's place: at each position of the same
        # prompts and tokens, the gap of the token the lower precision puts first
        stand_ins["control"] = {
            "token_gap_max": float(low_gaps.max()),
            "token_gap_mean": float(low_gaps.mean()),
            "served_not_best_share": float((low_gaps > 0).mean())}
    return out, stand_ins
