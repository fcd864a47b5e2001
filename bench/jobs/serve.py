"""The serving job: ``Scheduler.tick`` driven by an open loop.

Set-up makes the weights on the device from the seed (one jitted call),
builds one ``Scheduler`` (the slot pool, the decode and prefill-chunk
programs) and warms every program the mix can use by serving requests
whose prompts cover each prefill-chunk shape.  The window submits each
request when it falls due, ticks while there is work, and stamps each
request's tokens after the tick that produced them; after the close it
keeps ticking until every request due in the window has finished (up to
a minute), so late answers count as late.  Time to first token is taken
from when a request was due, not from when it was submitted.

``correct``: a sample of finished requests drawn from the seed, the
longest among them, run through the plain reference's full forward over
prompt and served tokens; the number compared is the widest gap by which
a served (greedy) token's logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import gen
import spec

DRAIN_S = 60.0


def _sspec(cfg, mix):
    from repro.serve.spec import ServeSpec
    return ServeSpec(arch=cfg.name, reduced=False, **mix["serve_spec"])


def setup(cell, seed, trace_host, program=None):
    import jax
    from repro.serve.scheduler import Scheduler

    cfgfile, mix = cell["config_file"], cell["mix"]
    cfg = spec.arch_config(cfgfile)
    model = spec.load_module("reference", cfgfile["reference"])
    wkey = jax.random.PRNGKey(spec.derive_seed(seed, "weights"))
    params = jax.jit(lambda k: model.init(cfgfile["config"], k))(wkey)
    sched = (program(Scheduler) if program else Scheduler)(
        _sspec(cfg, mix), params)
    rng = np.random.default_rng([int(seed) % (1 << 64), 4])
    for n, new in gen.longest_first(mix):
        sched.submit(rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
                     new)
    sched.drain()
    sched.completed.clear()
    for k in sched.stats:
        sched.stats[k] = 0
    jax.block_until_ready(sched.pool)
    return {"sched": sched, "model": model, "wkey": wkey, "cfg": cfg,
            "seed": seed}


def window(st, cell, seconds, trace_host, traced=False):
    mix = cell["mix"]
    if traced:
        seconds = min(seconds, mix["trace_seconds"])
    sched = st["sched"]
    reqs = gen.open_loop(mix, st["cfg"].vocab_size, st["seed"], seconds)
    live, late, stamps = {}, [], {}
    nxt, ticks = 0, 0
    t0 = time.perf_counter()
    deadline = seconds + DRAIN_S
    while True:
        now = time.perf_counter() - t0
        while nxt < len(reqs) and reqs[nxt][0] <= now:
            due, prompt, new = reqs[nxt]
            with trace_host("bench.submit"):
                live[nxt] = sched.submit(prompt, new, uid=nxt)
            late.append(now - due)
            stamps[nxt] = []
            nxt += 1
        if sched.busy:
            with trace_host("bench.tick"):
                sched.tick()
            ticks += 1
            t = time.perf_counter() - t0
            for i, r in list(live.items()):
                while len(stamps[i]) < len(r.tokens):
                    stamps[i].append(t)
                if r.t_done is not None:
                    del live[i]
        elif nxt >= len(reqs):
            break
        else:
            with trace_host("bench.wait"):
                time.sleep(max(0.0, min(reqs[nxt][0] - now, 0.002)))
        if now > deadline:
            break
    wall = time.perf_counter() - t0
    ttft, itl, failed = [], [], 0
    for i, (due, prompt, new) in enumerate(reqs):
        s = stamps.get(i, [])
        if len(s) < new:
            failed += 1
            ttft.append(float("inf"))
            continue
        ttft.append(s[0] - due)
        itl.extend(np.diff(s).tolist())
    st["done"] = [(reqs[r.uid][1], r.tokens) for r in sched.completed]
    late_ms = 1e3 * np.asarray(late or [0.0])
    return {"attempted": len(reqs), "failed": failed, "ttft": ttft,
            "itl": itl, "wall": wall, "steps": ticks,
            "prefill_tokens": int(sum(len(p) - 1 for _, p, _ in reqs)),
            "notes": [f"window: {len(reqs)} requests over {seconds} s, "
                      f"{ticks} ticks, {failed} unfinished, wall {wall!r} s",
                      f"generator lateness ms: median "
                      f"{float(np.median(late_ms))!r} p99 "
                      f"{float(np.percentile(late_ms, 99))!r} max "
                      f"{float(late_ms.max())!r}"]}


def release(st):
    st.pop("sched", None)
    gc.collect()


def _sample(done, seed, n):
    """n finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    order = np.argsort([-(len(p) + len(t)) for p, t in done], kind="stable")
    rng = np.random.default_rng([int(seed) % (1 << 64), 5])
    rest = rng.permutation(order[1:])[:n - 1]
    return [done[i] for i in [order[0], *rest]]


def gap_fn(model, cfgd, prec):
    """(params, tokens (b, L)) -> (b, L-1): at each position the gap by
    which the next token's reference logit lies below the reference's
    best; with ``prec`` other than f32 (the control) the token taken is
    the one that precision puts first instead of the one served."""
    import jax
    import jax.numpy as jnp

    from ref_common import Precision

    ref = Precision("f32")
    low = Precision(prec)

    @jax.jit
    def block(params, toks):
        lg = model.logits(cfgd, params, toks, ref)
        best = jnp.max(lg, axis=-1)
        if prec == "f32":
            pick = toks[:, 1:]
        else:
            pick = jnp.argmax(model.logits(cfgd, params, toks, low),
                              axis=-1)[:, :-1]
        got = jnp.take_along_axis(lg[:, :-1], pick[..., None], -1)[..., 0]
        return best[:, :-1] - got
    return block


def reference(st, cell, seed, prec="f32"):
    import jax
    import jax.numpy as jnp

    mix = cell["mix"]
    cfgd = cell["config_file"]["config"]
    model = st["model"]
    length = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    sample = _sample(st["done"], seed, mix["check_requests"])
    params = jax.jit(lambda k: model.init(cfgd, k))(st["wkey"])
    fn = gap_fn(model, cfgd, prec)
    rows = mix["check_rows"]
    worst = []
    for i in range(0, len(sample), rows):
        blk = sample[i:i + rows]
        toks = np.zeros((rows, length), np.int32)
        for j, (p, t) in enumerate(blk):
            seq = np.concatenate([p, np.asarray(t, np.int32)])
            toks[j, :len(seq)] = seq
        g = np.asarray(fn(params, jnp.asarray(toks)))
        for j, (p, t) in enumerate(blk):
            worst.append(float(g[j, len(p) - 1:len(p) - 1 + len(t)].max()))
    return {"gaps": worst, "tokens": int(sum(len(t) for _, t in sample))}


def compare(st, ref):
    return {"served_logit_gap": max(ref["gaps"]) if ref["gaps"]
            else float("inf"),
            "_where": {"requests_checked": len(ref["gaps"]),
                       "tokens_checked": ref["tokens"]}}


def control(st, cell, seed, ref, **planted):
    """The numbers when the control's choice of token (the reference in
    a lower precision) stands in for the served token."""
    return compare(st, reference(st, cell, seed, **planted))


def _p95(xs):
    xs = np.asarray(xs, np.float64)
    if xs.size == 0:
        return float("inf")
    return float(np.percentile(xs, 95, method="higher"))


def end_to_end(cell, work, device) -> dict:
    return {"serve_ttft_p95_ms": 1e3 * _p95(work["ttft"]),
            "serve_itl_p95_ms": 1e3 * _p95(work["itl"])}
