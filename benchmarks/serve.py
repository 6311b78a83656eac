"""A serving cell: ``DecodeServer`` under a load source, one thread.

``DecodeServer`` is tick-driven, so generator and server share one thread:
submit everything that is due, then ``tick()``; sleep to the next due time
only when nothing is pending.  The generator kinds (``generators/``) differ
only in their *source*: when the next request is due.

A source has ``start(t)``, ``due(now) -> [Request]``, ``next_due() ->
time | None``, ``completed(rid, now)`` and ``stop()``.

Phases on one clock: set-up (weights, warm-up, ramp) | window | grace |
correctness.  With ``--trace 1`` the last ``trace_slice_s`` of the window
run under ``jax.profiler``, so that the profiler's slow stop falls outside
the window; per-request statistics of that run use only requests due
before the profiler started."""
from __future__ import annotations

import collections
import os
import time

import numpy as np

from . import common, requests, trace
from .common import log
from .traffic_gen import warmup_buckets


# what the driver notes after every tick(): its own stamp, the program's
# two gauges, the turn of the driver's loop (a turn without a tick slept),
# and the requests that held a slot in the tick, by their status
Sample = collections.namedtuple(
    "Sample", "t queue_depth slot_occupancy turn held")


class Driver:
    def __init__(self, srv, source, telemetry):
        self.srv, self.source, self.tl = srv, source, telemetry
        self.records: dict = {}        # rid -> record (requests.py)
        self.open: set = set()         # rids not yet seen finished
        self.bad: dict = {}            # rid -> status other than ok
        self.samples: list = []        # one Sample a tick()
        self.turn = 0

    def submit_due(self, now: float) -> None:
        for req in self.source.due(now):
            t_sub = time.perf_counter()
            with trace.annotate("bench.submit"):
                rid = self.srv.submit(req.prompt,
                                      max_new_tokens=req.out_len)
            self.records[rid] = {
                "rid": rid, "t_due": req.t_due, "t_submit": t_sub,
                "prompt_len": len(req.prompt), "out_len": req.out_len,
                "prompt": req.prompt}
            self.open.add(rid)

    def step(self, now: float) -> None:
        srv = self.srv
        self.turn += 1
        self.submit_due(now)
        if srv.pending():
            with trace.annotate("bench.tick"):
                srv.tick()
            with trace.annotate("bench.poll"):
                t = time.perf_counter()
                self.samples.append(Sample(
                    t, self.tl.gauge("serving.queue_depth").get(),
                    self.tl.gauge("serving.slot_occupancy").get(),
                    self.turn, self._poll(t)))
        else:
            nxt = self.source.next_due()
            wait = 0.02 if nxt is None else min(0.02, nxt - now)
            if wait > 0:
                with trace.annotate("bench.sleep"):
                    time.sleep(wait)

    def _poll(self, now: float) -> int:
        """Retire what the tick finished; the requests that held a slot in
        it (active, or finished by it)."""
        held = 0
        for rid in list(self.open):
            st = self.srv.status(rid)
            held += st != "queued"
            if st in ("queued", "active"):
                continue
            self.open.discard(rid)
            if st != "ok":
                self.bad[rid] = st
            self.source.completed(rid, now)
        return held

    def run_until(self, t_end: float, stop=None) -> None:
        while True:
            now = time.perf_counter()
            if now >= t_end or (stop is not None and stop()):
                return
            self.step(now)


def build_server(fam, config: dict, seed: int):
    import jax

    t0 = time.perf_counter()
    cfg, params = fam.weights(config, common.jax_seed(seed))
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t0
    return params, fam.server(config, cfg, params), t_weights


def prime_block_copy(srv, vocab: int) -> None:
    """Set-up: make the server copy one KV block, so that its
    ``kv_copy@1`` executable exists before the window.  ``warmup()`` does
    not reach it, and fresh random prompts do: the prefix index matches
    token by token, so a prompt whose first token equals a cached
    prompt's adopts that row and copies the block on its first write."""
    first = np.arange(16, dtype=np.int32) % vocab
    second = first.copy()
    second[8:] = (second[8:] + 1) % vocab
    for prompt in (first, second):
        srv.submit(prompt, max_new_tokens=2)
        while srv.pending():
            srv.tick()


def check_served(fam, params, config, sample) -> tuple:
    """Teacher-force each (prompt, served) through the family's plain
    reference: (worst margin below the reference's best logit, tokens
    checked)."""
    worst, n = 0.0, 0
    for prompt, served in sample:
        m = fam.served_margins(config, params, prompt, served)
        if not np.isfinite(m).all():
            return float("inf"), n
        worst = max(worst, float(m.max()))
        n += len(served)
    return worst, n


def run(ctx: dict, make_source) -> None:
    """One run of a serving cell; prints the earlier lines and the last."""
    import jax

    from paddle_tpu import telemetry
    from paddle_tpu.framework import platform

    cell, args = ctx["cell"], ctx["args"]
    config, mix = cell["config"], cell["traffic"]
    devs = ctx["devices"]
    log(f"[setup] jax {jax.__version__}, compile cache at "
        f"{platform.init_compile_cache()}")
    fam = common.family(config)
    s = fam.sizes(config)
    seconds = float(args.seconds)

    # ---- set-up: weights, warm-up of this mix's shapes, ramp -------------
    params, srv, t_weights = build_server(fam, config, args.seed)
    source = make_source(mix, args.seed, s, seconds, args.rehearse)
    t0 = time.perf_counter()
    buckets = warmup_buckets(source.prompt_lens())
    timings = srv.warmup(prompt_lens=buckets)
    t_warm = time.perf_counter() - t0
    log(f"[setup] weights {t_weights:.2f}s; warm-up {t_warm:.2f}s of "
        f"prompt buckets {buckets}: {timings}")
    prime_block_copy(srv, s["V_published"])
    telemetry.reset()
    drv = Driver(srv, source, telemetry)
    t_ramp = time.perf_counter()
    source.start(t_ramp)
    # the window's edges are the schedule's own, so every seed's window
    # holds the same requests; the counters are read at the first loop
    # turn past each edge (no token is generated between turn and reading)
    t_win0 = t_ramp + float(mix["ramp_s"])
    t_win1 = t_win0 + seconds
    t_stats1 = t_win1 - (float(mix["trace_slice_s"]) if args.trace else 0.0)
    drv.run_until(t_win0)

    # ---- the window -------------------------------------------------------
    t_read0 = time.perf_counter()
    setup_s = t_read0 - ctx["t_process_start"]
    n0 = common.compile_count()
    tok0 = telemetry.snapshot()["counters"].get("serving.tokens_generated", 0)
    drv.run_until(t_stats1)
    slice_ = None
    if args.trace:
        slice_ = trace.Slice(os.path.join(ctx["scratch"], "trace"))
        slice_.start()
        drv.run_until(t_win1)
    t_read1 = time.perf_counter()
    tok1 = telemetry.snapshot()["counters"].get("serving.tokens_generated", 0)
    compiles = common.compile_count() - n0
    if compiles:
        log(f"[window] compiled in the window (telemetry's log, newest "
            f"last): {[c.get('name') for c in telemetry.snapshot()['compiles']][-compiles:]}")
    reduced = (trace.reduce(trace.extract(slice_.stop()))
               if slice_ is not None else None)
    # a request due while the window's last tick blocked is still sent
    drv.submit_due(t_win1)
    source.stop()

    # ---- grace: every request due in the window gets its first token -----
    def all_started():
        return not any(srv.status(rid) == "queued" for rid in drv.open
                       if t_win0 <= drv.records[rid]["t_due"] < t_win1)

    drv.run_until(time.perf_counter() + float(mix["grace_s"]),
                  stop=all_started)
    device = common.device_report(devs)
    joined = requests.join(drv.records.values(), requests.ring_events())
    counters = telemetry.snapshot()["counters"]

    # ---- what the window held --------------------------------------------
    due = requests.due_in(joined, t_win0, t_win1)
    failed = [r for r in due
              if r["rid"] in drv.bad or "t_first" not in r]
    done = requests.completed_in(joined, t_win0, t_win1)
    ttft = requests.spans_ms(requests.due_in(joined, t_win0, t_stats1),
                             "t_due", "t_first")
    tpot = requests.tpot_ms(requests.completed_in(joined, t_win0, t_stats1))
    late = requests.spans_ms(requests.due_in(joined, t_win0, t_stats1),
                             "t_due", "t_submit")
    window = t_read1 - t_read0
    values = {
        "setup_s": setup_s,
        "tpot_p50_ms": common.median(tpot) if tpot else None,
        "compiles_in_window": compiles,
    }
    in_win = [row for row in drv.samples if t_win0 <= row.t < t_win1]
    qd = [row.queue_depth for row in in_win]
    quarter = max(1, len(qd) // 4)
    log(f"[window] {window:.2f}s; due {len(due)} requests "
        f"({len(due) / window:.2f}/s offered), completed {len(done)} "
        f"({len(done) / window:.2f}/s), failed {len(failed)}, statuses "
        f"other than ok {sorted(set(drv.bad.values()))}; generated "
        f"{(tok1 - tok0) / window:.1f} tokens/s; ticks {len(qd)}; slots "
        f"occupied, mean {np.mean([row.slot_occupancy for row in in_win]) if in_win else None}")
    log(f"[window] samples: ttft n={len(ttft)} (requests due), tpot "
        f"n={len(tpot)} (requests retired); ttft p50/p95 {_pcts(ttft)} ms; "
        f"tpot p50/p95 {_pcts(tpot)} ms; generator lateness p50/p95/max "
        f"{_pcts(late)}/{max(late) if late else None} ms")
    log(f"[window] queue depth, mean of quarter 2 / quarter 4: "
        f"{np.mean(qd[quarter:2 * quarter]) if qd else None} / "
        f"{np.mean(qd[-quarter:]) if qd else None}; rejected "
        f"{counters.get('serving.requests_rejected', 0)}, shed "
        f"{counters.get('serving.requests_shed', 0)}, failed "
        f"{counters.get('serving.requests_failed', 0)}, prefix evictions "
        f"{counters.get('kv_pool.prefix_evictions', 0)}, slot evictions "
        f"{counters.get('resilience.oom_evictions', 0)}, admit blocked "
        f"{counters.get('kv_pool.admit_blocked', 0)}; executables "
        f"compiled in the window: {compiles}")
    log(f"[setup] setup_s {setup_s:.2f}, of which weights {t_weights:.2f}, "
        f"warm-up {t_warm:.2f}, ramp {t_win0 - t_ramp:.2f}")

    # ---- correctness, outside every timing -------------------------------
    corr = config["correctness"]
    rng = np.random.default_rng(args.seed)
    pool = [r for r in done if r["rid"] not in drv.bad]
    picks = rng.permutation(len(pool))[:corr["sample_requests"]]
    sample = [(pool[i]["prompt"], srv.result(pool[i]["rid"])) for i in picks]
    wrong_len = sum(len(out) != pool[i]["out_len"]
                    for i, (_, out) in zip(picks, sample))
    srv.close()
    t0 = time.perf_counter()
    worst, n_tok = check_served(fam, params, config, sample)
    log(f"[correct] {len(sample)} served requests, {n_tok} tokens "
        f"teacher-forced through the {config['family']} family's reference "
        f"in {time.perf_counter() - t0:.1f}s: worst margin below the "
        f"reference's best logit {worst:.4f} (tolerance "
        f"{corr['logit_margin_tol']}); outputs of another length than asked "
        f"for: {wrong_len}")
    compared = {"worst_logit_margin": (worst, corr["logit_margin_tol"]),
                "outputs_of_wrong_length": (wrong_len, 0),
                "no_request_to_sample": (0 if sample else 1, 0)}

    common.emit(ctx, values, compared, len(due), len(failed), device, {
        "joined": joined, "samples": drv.samples, "window": (t_win0, t_win1),
        "stats_window": (t_win0, t_stats1), "slice": (t_stats1, t_win1),
        "trace": reduced, "sizes": s})


def _pcts(xs):
    if not xs:
        return None
    return (f"{common.percentile(xs, 50):.1f}/"
            f"{common.percentile(xs, 95):.1f}")
