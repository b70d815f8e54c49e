"""One closed-loop serving cell: ``Server.run`` over a backlog.

As many callers as slots, each sending its next request when a slot
frees: the plain loop of ``runtime/serving.py``.  The backlog is a fixed
amount of work drawn from the seed — ``requests_per_second`` of the mix
times ``--seconds`` requests, the mix's lengths at their quantiles — so
the window lasts about ``--seconds`` at the rate measured when the mix
was written, and every seed does the same work in another order.

Everything timed is read from the program's telemetry stream on the
wall clock.  After the window, with caches and weights freed, the plain
reference runs once over a seeded sample of finished requests.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from benchmark import common, workload_gen
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import Request, Server, ServingExecutor


def request_times(events: List[Dict[str, Any]]) -> Dict[int, Dict[str, Any]]:
    """``id -> {"start", "end", "tokens", "error"}`` from ``request_start``
    (stamped at admission) and ``request_end``."""
    out: Dict[int, Dict[str, Any]] = {}
    for e in events:
        if e["ev"] == "request_start":
            out.setdefault(e["id"], {})["start"] = e["ts"]
        elif e["ev"] == "request_end":
            out.setdefault(e["id"], {}).update(end=e["ts"], tokens=e["tokens"], error=e.get("error"))
    return out


def run(ctx) -> Dict[str, Any]:
    cfg, traffic, fam = ctx.config, ctx.traffic, ctx.family
    t_run = time.time()
    if "serve" not in ctx.reuse:
        ff, ffcfg = fam.build_serve(cfg, traffic)
        ctx.reuse["serve"] = ServingExecutor(
            ff, ffcfg, max_batch=traffic["slots"], max_seq=traffic["max_seq"],
            buckets=traffic["buckets"], decode_kernel=traffic["decode_kernel"],
        )
    sex = ctx.reuse["serve"]
    ff, ffcfg = sex.model, sex.config
    abstract, _, state_avals = jax.eval_shape(Executor(ff, config=ffcfg).init)
    if jax.tree.leaves(state_avals):
        raise SystemExit("this runner drives graphs without op state")
    spec = fam.leaf_spec(cfg, traffic)
    one = jax.sharding.SingleDeviceSharding(sex.device)
    params = common.make_params(spec, ctx.seed, abstract,
                                jax.tree.map(lambda _: one, abstract))
    srv = Server(sex, params, {}, decode_steps=traffic["decode_steps"])
    common.stamp(t_run, "seeded parameters on the device")

    def to_requests(rows):
        return [Request(id=r["id"], prompt=r["prompt"], max_new_tokens=r["max_new_tokens"])
                for r in rows]

    # The telemetry stream opens before the warm-up: the program lowers
    # each program once more for its ``program_cost`` event the first
    # time a stream sees it, and that belongs to set-up, not the window.
    tel = telemetry.Telemetry(directory=ctx.telemetry_dir, meta={"cell": ctx.cell["name"]})
    seconds = ctx.window_seconds()
    n = max(traffic["slots"], int(round(traffic["requests_per_second"] * seconds)))
    backlog = workload_gen.closed_backlog(traffic, n, ctx.seed, cfg["vocab_size"])
    plens = np.array([len(r["prompt"]) for r in backlog])
    buds = np.array([r["max_new_tokens"] for r in backlog])
    common.say(f"[serve] backlog {n} requests: prompts mean {plens.mean():.1f} "
               f"p50 {np.median(plens):.0f} max {plens.max()}, budgets mean {buds.mean():.1f} "
               f"p50 {np.median(buds):.0f} max {buds.max()}, {int(buds.sum())} tokens to generate")
    requests = to_requests(backlog)

    with tel:
        # -- warm-up: every prefill bucket, the install and the decode program
        warm = [{"id": i, "prompt": np.full((min(b, traffic["max_seq"] - 16) - 1,), 7 + i, np.int32),
                 "max_new_tokens": 2 * traffic["decode_steps"]}
                for i, b in enumerate(sex.buckets)]
        _, wstats = srv.run(to_requests(warm))
        if wstats["failed"]:
            raise SystemExit(f"warm-up failed: {wstats}")
        common.stamp(t_run, "warm")
        # -- the window ------------------------------------------------------
        with ctx.profile(), ctx.compiles.window():
            ctx.mark_window_start()
            t0 = time.perf_counter()
            with TraceAnnotation("bench/server_run"):
                results, _stats = srv.run(requests)
            elapsed = time.perf_counter() - t0
    peak = common.peak_memory_bytes([sex.device])
    events = [e for e in common.read_events(tel.path) if e["ts"] >= ctx.window_wall0]
    times = request_times(events)
    done = {i: t for i, t in times.items() if t.get("error") is None and t.get("tokens")}
    failed = n - len(done)
    tokens = sum(t["tokens"] for t in done.values())
    first = min(t["start"] for t in times.values())
    last = max(t["end"] for t in times.values())
    tpot = [(t["end"] - t["start"]) / t["tokens"] * 1e3 for t in done.values()]
    supersteps = [e for e in events if e["ev"] == "decode_superstep"]
    occ = [e["active"] for e in supersteps]
    common.say(f"[serve] window {elapsed:.3f} s ({last - first:.3f} s first admission to last "
               f"finish), {len(done)}/{n} requests completed, {tokens} tokens, "
               f"{len(occ)} decode supersteps, longest superstep "
               f"{max(e['wall_s'] for e in supersteps) * 1e3:.3f} ms (longest "
               f"from one's end to the next's "
               f"{max(np.diff([e['ts'] for e in supersteps]), default=0.0) * 1e3:.3f} ms), mean occupancy "
               f"{np.mean(occ) / traffic['slots']:.3f} of {traffic['slots']} slots, "
               f"compiles in window {ctx.compiles.count}, peak {peak / 2**30:.2f} GiB")
    tpot_p50, tpot_p95 = (float(x) for x in np.percentile(tpot, [50, 95]))
    common.say(f"[serve] tpot ms over {len(tpot)} requests: p50 {tpot_p50:.3f} "
               f"p95 {tpot_p95:.3f} max {max(tpot):.3f}")

    # -- the reference, once caches and weights are freed -------------------
    served = {rid: list(r.tokens) for rid, r in results.items() if r.error is None}
    del params, srv, results
    check = common.Check()
    by_len = sorted(served, key=lambda i: len(backlog[i]["prompt"]) + len(served[i]))
    rng = np.random.default_rng([int(ctx.seed), 3])
    pick = {by_len[-1]} | {int(i) for i in rng.choice(by_len, size=min(
        int(traffic["check_requests"]), len(by_len)), replace=False)}
    samples = [{"prompt": backlog[i]["prompt"], "tokens": served[i]} for i in sorted(pick)]
    t_ref = time.perf_counter()
    ref = fam.reference.served_gaps(cfg, ctx.seed, traffic["max_seq"], samples)
    common.say(f"[serve] reference read {ref['tokens']} served tokens of {len(samples)} requests "
               f"in {time.perf_counter() - t_ref:.1f} s, mean gap {ref['mean_gap']:.6g}")
    check.add("served_logit_gap", ref["widest_gap"], traffic["limits"]["served_logit_gap"])
    check.add("failed_requests", float(failed), 0.0)
    if ctx.control:
        ctl = fam.reference.served_gaps(cfg, ctx.seed, traffic["max_seq"], samples, quant=True)
        common.say(f"[control] served_logit_gap = {ctl['widest_gap']:.6g} mean {ctl['mean_gap']:.6g}")
    budget_ok = all(len(served[i]) == backlog[i]["max_new_tokens"] for i in served)
    check.add("requests_short_of_budget", float(not budget_ok), 0.0)

    return {
        "correct": check.correct, "attempted": n, "failed": failed,
        "quantities": {"tokens_per_s": tokens / (last - first),
                       "tpot_ms_p95": tpot_p95, "tpot_ms_p50": tpot_p50},
        "peak_bytes": peak, "window_s": elapsed, "telemetry_path": tel.path, "backlog": backlog,
    }
