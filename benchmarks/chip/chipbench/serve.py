"""The serving loop shared by the serve kinds, and the serve cell's run.

A ``Stepper`` steps the program's paged ``Engine`` and, after every step,
makes that step's tokens visible on the host (``block_until_ready`` on
the step's outputs), as a streaming server must; each token is timed
from that moment, the first from the engine's own ``t_first``. It reads
the prefill work of every step from the engine itself: the chunk of its
own plan that each chunk call runs, and the prompt tails of each packed
call, with the prefix hit that admission found.

Host spans (``jax.profiler.TraceAnnotation``) mark submit, the engine
step, the token sync and the generator's wait; a traced run names its
device idle gaps by them.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from . import devtrace, harness, reference, weights

# pending-token counts whose stack the engine may build (see build)
WARM_PENDING = 160
SPANS = ("submit", "engine_step", "token_sync", "generator_wait",
         "traced_window")


class Rec:
    """One request as the benchmark sees it."""

    __slots__ = ("phase", "sched", "prompt", "max_new", "rid", "req",
                 "times", "client")

    def __init__(self, phase, sched, prompt, max_new, client=None):
        self.phase, self.sched = phase, sched
        self.prompt, self.max_new = prompt, max_new
        self.client = client
        self.rid = self.req = None
        self.times = []


class Stepper:
    def __init__(self, eng):
        import jax

        self.jax = jax
        self.eng = eng
        self.by_rid = {}
        self.steps = []
        self.finished = []
        self.t_zero = None
        self._prefill = []    # (start, n_real) computed in this step
        self._admitted = []   # (prompt_len, hit) admitted in this step
        self._watch_prefill(eng)

    def _watch_prefill(self, eng):
        """Record what the engine's own prefill calls compute: the
        (start, width, n_real) entry of its chunk plan that each chunk
        call runs, and the tails of each packed call past their hits."""
        chunk_step, start_chunking = eng._chunk_step, eng._start_chunking
        packed = eng._packed_prefill

        def on_chunk():
            st = eng._chunking
            start, _, n_real = st["plan"][st["i"]]
            self._prefill.append((start, n_real))
            chunk_step()

        def on_start(slot, req, row, hit):
            self._admitted.append((len(req.tokens), hit))
            start_chunking(slot, req, row, hit)

        def on_packed(admitted):
            for _, req, _, hit in admitted:
                self._admitted.append((len(req.tokens), hit))
                self._prefill.append((hit, len(req.tokens) - hit))
            packed(admitted)

        eng._chunk_step = on_chunk
        eng._start_chunking = on_start
        eng._packed_prefill = on_packed

    def span(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def submit(self, rec: Rec, t0: float) -> None:
        with self.span("submit"):
            rec.rid = self.eng.submit(rec.prompt, max_new=rec.max_new)
            rec.req = self.eng.queue[-1]
        self.by_rid[rec.rid] = rec

    def step(self) -> list:
        """One engine step and its token sync; returns finished Recs."""
        eng = self.eng
        self._prefill, self._admitted = [], []
        dec0 = eng.n_decode_steps
        t0 = time.perf_counter()
        with self.span("engine_step"):
            retired = eng.step()
        with self.span("token_sync"):
            self.jax.block_until_ready((eng.tok, eng.ws))
        t1 = time.perf_counter()
        decoded = eng.n_decode_steps > dec0
        ctxs = []
        for i, r in enumerate(eng.slots):
            if r is None:
                continue
            rec = self.by_rid[r.rid]
            if not rec.times:
                rec.times.append(r.t_first)
            if decoded:
                rec.times.append(t1)
                ctxs.append(int(eng.pkv.lens[i]))
        done = []
        for res in retired:
            rec = self.by_rid[res["rid"]]
            if not rec.times:
                rec.times.append(rec.req.t_first)
            if decoded:
                rec.times.append(t1)
                ctxs.append(res["prompt_len"] + res["n_new"] - 1)
            done.append(rec)
            self.finished.append((rec, res))
        self.steps.append({"t0": t0, "t1": t1, "decode": decoded,
                           "ctxs": ctxs, "prefill": self._prefill,
                           "admitted": self._admitted})
        return done


class _CompileCounter:
    """Times of the backend compiles that JAX reports."""

    def __init__(self):
        import jax

        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def count_between(self, lo, hi):
        return sum(lo <= t < hi for t in self.times)


def _lat_metrics(recs, win0, win1, seconds):
    """out_tok_s, ttft_p90_ms and itl_p95_ms over the window; a cell
    reports those that BENCHMARK.json declares for it."""
    toks = 0
    ttft, itl = [], []
    for r in recs:
        for j, t in enumerate(r.times):
            if win0 <= t < win1:
                toks += 1
                if j:
                    itl.append((t - r.times[j - 1]) * 1e3)
        if r.phase == "window":
            ttft.append(((r.times[0] - r.sched) * 1e3) if r.times
                        else math.inf)
    return {"out_tok_s": toks / seconds,
            "ttft_p90_ms": harness.percentile(ttft, 90),
            "itl_p95_ms": harness.percentile(itl, 95)}, len(ttft)


def build(ctx: dict):
    """The served weights from the seed and the warmed paged engine."""
    import jax

    from repro.runtime.engine import Engine

    tf, m = ctx["tf"], ctx["m"]
    cfg = harness.program_config(ctx["conf"])
    params = weights.served_tree(cfg, m, ctx["seed"])
    harness.log(f"weights {weights.served_bytes(params) / 2**30:.3f} GiB "
                f"served, {time.perf_counter() - ctx['t_proc']:.1f} s")
    page = int(tf["page_size"])
    eng = Engine(params, cfg, capacity=int(tf["slots"]),
                 max_len=int(tf["max_len"]),
                 kv_pages=int(tf["pool_tokens"]) // page + 1,
                 page_size=page, max_chunk=int(tf["max_chunk"]),
                 prefix_cache=True, rng=jax.random.PRNGKey(0))
    # the engine stacks its pending tokens in one op whose shape grows
    # with the steps since the last retirement: warm those shapes too
    import jax.numpy as jnp

    for k in range(1, WARM_PENDING + 1):
        jnp.stack([eng.tok[:, 0]] * k).block_until_ready()
    harness.log(f"engine warm, {time.perf_counter() - ctx['t_proc']:.1f} s")
    return params, eng


def serve_loop(ctx: dict, eng, loop) -> dict:
    """Run ``loop`` on the engine: its set-up, the warm phase, the window
    of ``ctx["seconds"]`` and the drain; with ``ctx["trace"]`` a profile
    of ``trace_steps`` steps from the middle of the window."""
    tf = ctx["tf"]
    drv = Stepper(eng)
    ctx["stepper"] = drv
    loop.setup(drv)
    seconds = ctx["seconds"]
    t_zero = drv.t_zero = time.perf_counter()
    loop.start(drv, t_zero)
    win0 = t_zero + loop.warm_s
    win1 = win0 + seconds
    drain_end = win1 + float(tf["drain_max_s"])
    trace_at = win0 + seconds * float(tf["trace_at"])
    traced = {} if ctx["trace"] else None
    trace_steps = int(tf["trace_steps"])
    tcm = win_span = None
    n_traced = 0
    trace_first = None
    compiles = _CompileCounter()
    while True:
        now = time.perf_counter()
        loop.arrive(drv, now - t_zero)
        if now >= win1 and (loop.window_served(drv) or now >= drain_end):
            break
        if eng.idle:
            nxt = loop.next_due()
            with drv.span("generator_wait"):
                if nxt is None:
                    if now >= win1:
                        break
                    time.sleep(0.001)
                else:
                    time.sleep(max(0.0, min(t_zero + nxt - now, 0.05)))
            continue
        if traced is not None and tcm is None and now >= trace_at:
            tcm = devtrace.capture(traced)
            tcm.__enter__()
            win_span = drv.span("traced_window")
            win_span.__enter__()
            trace_first = len(drv.steps)
        for rec in drv.step():
            loop.on_done(drv, rec, time.perf_counter() - t_zero)
        if tcm is not None and n_traced < trace_steps:
            n_traced += 1
            if n_traced == trace_steps:
                win_span.__exit__(None, None, None)
                tcm.__exit__(None, None, None)
    if tcm is not None and n_traced < trace_steps:
        win_span.__exit__(None, None, None)
        tcm.__exit__(None, None, None)
    if traced is not None and tcm is None:
        raise harness.BenchError("the window ended before the trace began")
    harness.log(f"{compiles.count_between(win0, win1)} compiles inside the "
                f"window")
    return {"drv": drv, "win0": win0, "win1": win1, "traced": traced,
            "steps": (trace_first, (trace_first or 0) + n_traced)}


def run(ctx: dict, loop) -> tuple:
    """Set up, serve under ``loop``, measure, then check a sample of the
    finished requests against the reference. Returns (result, checks)."""
    import jax

    tf, m, seed = ctx["tf"], ctx["m"], ctx["seed"]
    seconds = ctx["seconds"]
    params, eng = build(ctx)
    out = serve_loop(ctx, eng, loop)
    drv, win0, win1, traced = (out["drv"], out["win0"], out["win1"],
                               out["traced"])
    trace_first, trace_end = out["steps"]

    recs = list(drv.by_rid.values())
    e2e, attempted = _lat_metrics(recs, win0, win1, seconds)
    harness.log(f"ttft_p90_ms {e2e['ttft_p90_ms']:.1f} over {attempted} "
                f"requests")
    setup_s = win0 - ctx["t_proc"]
    failed = sum(1 for r in recs if r.phase == "window" and not r.times)
    devs = ctx["devs"]

    # token accounting: every finished request got one timed token per
    # token the engine returned
    mismatch = sum(1 for rec, res in drv.finished
                   if len(rec.times) != res["n_new"])
    result = {"attempted": attempted, "failed": failed}
    lctx = None
    if traced is not None:
        lctx = _layer_ctx(ctx, drv, traced, trace_first, trace_end, eng,
                          win0, win1)
    dev = harness.device_info(devs, lctx["trace"] if lctx else None)
    samples = _sample(drv.finished, seed, int(tf["check_tokens"]))
    del eng, params, drv.eng
    loop.release()
    gc.collect()
    # unload the served programs too: a loaded TPU program keeps its
    # scratch reserved, which the reference needs
    jax.clear_caches()

    t_ref = time.perf_counter()
    # a control run puts the float8 reference's own greedy tokens in the
    # place of the served ones, judged by the same comparison
    control = bool(ctx.get("control"))
    if control:
        harness.log("control: the float8 reference's tokens stand in for "
                    "the served ones")
    gaps = reference.served_gaps(m, seed, samples, lowp=control)
    harness.log(f"served {len(drv.steps)} steps; reference over "
                f"{len(samples)} requests {time.perf_counter() - t_ref:.1f} s")
    max_gap = max(float(g.max()) for g in gaps) if gaps else math.inf
    limit = float(tf["max_gap_limit"])
    checks = {
        "max_gap": {"value": max_gap, "limit": limit},
        "served_tokens_checked": {"value": int(sum(len(s) for _, s in
                                                   samples)),
                                  "limit": int(tf["check_tokens"])},
        "token_count_mismatch": {"value": mismatch, "limit": 0},
    }
    correct = (max_gap <= limit and mismatch == 0
               and checks["served_tokens_checked"]["value"]
               >= checks["served_tokens_checked"]["limit"])
    result["correct"] = bool(correct)
    if traced is not None:
        metrics = harness.read_metrics(
            harness.cell_metrics(ctx["bench"], ctx["cell"]["name"], True),
            lctx)
        result["breakdown"] = lctx["trace"]["breakdown"]
    else:
        e2e["setup_s"] = setup_s
        units = dict(harness.cell_metrics(ctx["bench"], ctx["cell"]["name"],
                                          False))
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in e2e.items() if k in units}
    result["metrics"] = metrics
    result["device"] = dev
    return result, checks


def _sample(finished, seed: int, want: int) -> list:
    """(prompt, served tokens) of finished requests: the longest, then
    others in a seeded order until ``want`` served tokens are covered."""
    if not finished:
        return []
    items = [(rec.prompt, np.asarray(res["tokens"], np.int32))
             for rec, res in finished]
    longest = max(range(len(items)),
                  key=lambda i: len(items[i][0]) + len(items[i][1]))
    order = [longest] + [i for i in np.random.default_rng(seed).permutation(
        len(items)) if i != longest]
    out, n = [], 0
    for i in order:
        out.append(items[i])
        n += len(items[i][1])
        if n >= want:
            break
    return out


def _layer_ctx(ctx, drv, traced, s0, s1, eng, win0, win1) -> dict:
    """What the per-layer readers read: the traced steps, the reduced
    trace, the window's requests and the engine's geometry."""
    spans = devtrace.host_spans(traced, ("traced_window",))
    if not spans:
        raise harness.BenchError("the trace holds no traced_window span")
    _, lo, dur = spans[-1]
    red = devtrace.reduce(traced, lo, lo + dur, SPANS)
    return {
        "m": ctx["m"], "cell": ctx["cell"]["name"],
        "peaks": harness.peaks_for(ctx["devs"][0].device_kind),
        "chips": len(ctx["devs"]),
        "steps": drv.steps[s0:s1],
        "page_size": eng.page_size,
        "capacity": eng.capacity,
        "requests": [r for r in drv.by_rid.values()
                     if r.phase == "window"],
        "window": (win0, win1),
        "trace": red,
    }
