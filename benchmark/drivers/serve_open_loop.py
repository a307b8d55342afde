"""Traffic kind ``serve_open_loop``: requests arrive on a schedule fixed by
the mix and the seed, whether or not earlier ones have finished.

The generator runs in the benchmark's own loop: ``submit`` what is due,
then ``engine.step()``. Times are the benchmark's, from the instant a
request was *due* to the instant its token is visible to the caller of
``engine.step()`` (``engine.drain_dirty()``, the engine's public progress
feed). Arrivals stop at ``--seconds``; the loop then keeps stepping until
every request due in the window has finished (or ``drain_limit_s`` has
passed), so that the tails are the tails of *all* requests due in the
window. Output tokens per second count the tokens visible inside the
window over the window's seconds; the drain adds to neither.

A request that is refused, fails or is unfinished when the drain limit
passes counts as +inf in the TTFT tail and in ``failed``.

After the drain the engine and its parameters are freed, and the plain
float32 reference (``reference/decoder.py``), with the same weights made
again from the seed, runs once over prompt + served tokens of a seeded
sample of the finished requests, the longest among them; the number
compared is the widest greedy regret (``reference/check.py``).
"""

import math
import time

from benchmark import harness, stats, traffic, weights
from benchmark.reference import check


def build_engine(run):
    import jax

    import smdistributed_modelparallel_tpu as smp

    cfg = run.cell.config
    builder = run.cell.builder()
    smp.reset()
    smp.init(dict(cfg["smp"]), devices=list(run.devices))
    params = jax.jit(lambda seed: builder.tree_from_hf(
        cfg, weights.make_weights(cfg, seed)))(weights.seed_word(run.seed))
    engine = smp.serving.ServingEngine(
        builder.module(cfg), params=params, **cfg["serve"])
    return smp, engine


def serve(run, engine, smp, reqs, seconds, drain_limit_s):
    """The open loop. Returns per-request token times and what the loop
    counted."""
    token_t = {r["id"]: [] for r in reqs}
    refused, lateness, tick_s = [], [], []
    done = set()
    i, n = 0, len(reqs)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds + drain_limit_s:
            break
        if now < seconds:
            with run.span("submit"):
                while i < n and reqs[i]["due_s"] <= now:
                    r = reqs[i]
                    ok = engine.submit(smp.serving.ServeRequest(
                        r["id"], r["prompt"], r["max_new_tokens"]))
                    if not ok:
                        refused.append(r["id"])
                    lateness.append(now - r["due_s"])
                    i += 1
        elif len(done) + len(refused) >= i:
            break
        if not engine.busy:
            if i < n and reqs[i]["due_s"] < seconds:
                with run.span("wait_due"):
                    wait = reqs[i]["due_s"] - (time.perf_counter() - t0)
                    time.sleep(max(0.0, min(wait, 0.002)))
                continue
            # Nothing in flight and nothing more due: idle to the end of
            # the window.
            with run.span("wait_due"):
                time.sleep(0.002)
            continue
        t = time.perf_counter()
        with run.span("engine_step"):
            engine.step()
        t_after = time.perf_counter()
        if engine.last_tick_worked:
            tick_s.append(t_after - t)
        seen = t_after - t0
        for rid, rec in engine.drain_dirty():
            times = token_t.get(rid)
            if times is None:
                continue
            times.extend([seen] * (len(rec["tokens"]) - len(times)))
            if rec["done"]:
                done.add(rid)
    return {"token_t": token_t, "refused": refused,
            "lateness": lateness, "tick_s": tick_s, "done": done,
            "submitted": i}


def latency_metrics(reqs, loop, seconds):
    """TTFT per request due in the window (+inf where no token was seen or
    the request did not finish), pooled inter-token gaps, and output tokens
    visible inside the window."""
    ttft, gaps, tokens_in_window, failed = [], [], 0, 0
    for r in reqs:
        times = loop["token_t"][r["id"]]
        finished = r["id"] in loop["done"] \
            and len(times) == r["max_new_tokens"]
        failed += not finished
        ttft.append(1e3 * (times[0] - r["due_s"])
                    if finished else math.inf)
        gaps.extend(1e3 * (b - a) for a, b in zip(times, times[1:]))
        tokens_in_window += sum(1 for t in times if t <= seconds)
    return {
        "serve.ttft_p90_ms": stats.percentile(ttft, 90),
        "serve.itl_p95_ms": stats.percentile(gaps, 95) if gaps else math.inf,
        "serve.out_tokens_per_s": tokens_in_window / seconds,
    }, failed, {"ttft_ms": ttft, "n_gaps": len(gaps)}


def sample_for_check(reqs, loop, seed, count):
    """A seeded sample of the finished requests, the longest in it."""
    import numpy as np

    finished = [r for r in reqs if r["id"] in loop["done"]]
    if not finished:
        return []
    longest = max(finished,
                  key=lambda r: len(r["prompt"]) + r["max_new_tokens"])
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(seed), 0x636b])
    picked = rng.choice(len(rest), size=min(count - 1, len(rest)),
                        replace=False)
    return [longest] + [rest[int(j)] for j in picked]


def reference_regrets(cfg, seed, sample, results, precision="float32",
                      control=None):
    """The reference's greedy regret of every served token of the sample.
    With ``control`` (a lower precision) the tokens judged are not the
    served ones but those the lower precision puts first at each position
    of the same prompts and tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import decoder

    w = jax.jit(lambda s: weights.make_weights(cfg, s))(
        weights.seed_word(seed))
    T = cfg["n_positions"]

    @jax.jit
    def regret_rows(w, ids, chosen):
        logits = decoder.forward(cfg, w, ids[None], precision)[0]
        if control is not None:
            low = decoder.forward(cfg, w, ids[None], control)[0]
            picked = jnp.argmax(low, axis=-1)
        else:
            picked = chosen
        best = jnp.max(logits, axis=-1)
        return best - jnp.take_along_axis(
            logits, picked[:, None], axis=-1)[:, 0]

    out = {}
    for r in sample:
        tokens = list(r["prompt"]) + list(results[r["id"]])
        P, n = len(r["prompt"]), len(tokens)
        ids = np.zeros((T,), np.int32)
        ids[:n] = tokens
        # The token at position p+1 was chosen from the logits at p.
        chosen = np.zeros((T,), np.int32)
        chosen[:n - 1] = tokens[1:]
        rows = np.asarray(regret_rows(w, jnp.asarray(ids),
                                      jnp.asarray(chosen)))
        out[r["id"]] = rows[P - 1:n - 1]
    return out


def run(run):
    import jax

    mix, cfg = run.cell.traffic, run.cell.config
    smp, engine = build_engine(run)
    run.lap("build_engine_and_weights")
    reqs = traffic.requests(mix, run.seed, run.seconds, cfg["vocab_size"])
    # Warm-up: both programs, on this cell's own shapes (they are fixed:
    # one prefill chunk, one decode batch).
    engine.run([smp.serving.ServeRequest(
        "warmup", reqs[0]["prompt"][:40], 3)], timeout_s=1200)
    engine.drain_dirty()
    # The engine draws each request's sampling keys with one
    # ``jax.random.split(key, max_new_tokens)``: a program per distinct
    # length. Warm the lengths this traffic holds.
    for n in sorted({r["max_new_tokens"] for r in reqs}):
        jax.random.key_data(jax.random.split(jax.random.key(0), n))
    stats0 = dict(engine.stats)
    run.lap("warm_up_programs")
    harness.say("setup", requests=len(reqs), programs=sorted(engine._programs),
                prompt_tokens=sum(len(r["prompt"]) for r in reqs),
                output_tokens=sum(r["max_new_tokens"] for r in reqs))

    with run.window():
        loop = serve(run, engine, smp, reqs, run.seconds,
                     mix["drain_limit_s"])
    e2e, failed, detail = latency_metrics(reqs, loop, run.seconds)
    counted = {k: engine.stats[k] - stats0.get(k, 0) for k in engine.stats}
    programs = sorted(engine._programs)
    results = {rid: list(engine.results[rid]) for rid in loop["done"]}
    sample = sample_for_check(reqs, loop, run.seed, mix["check_requests"])
    engine.close()
    del engine
    smp.shutdown()

    t = time.perf_counter()
    regrets = reference_regrets(cfg, run.seed, sample, results)
    reference_s = time.perf_counter() - t
    if run.control:
        low = reference_regrets(cfg, run.seed, sample, results,
                                control=run.control)
        harness.say("control", precision=run.control, numbers={
            "greedy_regret_max": max(float(v.max()) for v in low.values())})
    widest = max((float(v.max()) for v in regrets.values()),
                 default=math.inf)
    numbers = {
        "greedy_regret_max": widest,
        "requests_not_finished": failed,
        "requests_refused": len(loop["refused"]),
        "programs_unexpected": len(set(programs) ^ {"prefill", "decode"}),
    }
    limits = check.load_limits(run.cell.manifest.dir, run.cell.name)
    correct, rows = check.judge(numbers, limits)
    finite = [x for x in detail["ttft_ms"] if math.isfinite(x)]
    harness.say(
        "compared", rows=rows, checked_requests=len(sample),
        checked_tokens=sum(len(v) for v in regrets.values()),
        reference_seconds=reference_s)
    harness.say(
        "loop", requests_due=len(reqs), submitted=loop["submitted"],
        finished=len(loop["done"]), engine_counts=counted,
        generator_late_ms_max=1e3 * max(loop["lateness"], default=0.0),
        generator_late_ms_median=1e3 * stats.median(loop["lateness"])
        if loop["lateness"] else 0.0,
        ttft_ms_median=stats.median(finite) if finite else None,
        itl_gaps=detail["n_gaps"], ticks=len(loop["tick_s"]))

    # A request's first token comes from its last prefill chunk; the rest
    # are the decode batch's.
    decode_tokens = sum(
        max(0, len(v) - 1) for v in loop["token_t"].values())
    return {
        "correct": correct, "attempted": len(reqs), "failed": failed,
        "end_to_end": e2e,
        "context": {
            "tick_s": loop["tick_s"], "decode_tokens": decode_tokens,
            "prompt_tokens": sum(
                len(r["prompt"]) for r in reqs[:loop["submitted"]]),
            "decode_steps": counted.get("decode_steps", 0),
            "prefill_chunks": counted.get("prefill_chunks", 0),
            "max_slots": cfg["serve"]["max_slots"],
        },
    }
