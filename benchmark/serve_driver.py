"""The ``serve`` driver: one run of a serving cell.

Drives ``Server.serve_http`` over loopback in the process that holds the
chip; the load comes from a child process (``loadgen.py``) that never imports
JAX and posts to ``/v1/stream``.  Every end-to-end time is the client's.
The engine's own samples (``ServingMetrics``) are read through a subclass
that stamps each with the time it was recorded, so that per-layer metrics
cover the measured window and nothing else.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark import harness, loadgen, trace_reduce
from benchmark.harness import BenchError

def _stamped_metrics():
    from ml_trainer_tpu.serving.metrics import ServingMetrics

    class StampedMetrics(ServingMetrics):
        """The program's counters, each sample kept with its time."""

        def __init__(self):
            super().__init__()
            self.stamped = {"step": [], "prefill": [], "queue_wait": []}

        def record_step(self, seconds, active_slots, total_slots, tokens):
            self.stamped["step"].append(
                (time.monotonic(), float(seconds),
                 active_slots / total_slots if total_slots else 0.0))
            super().record_step(seconds, active_slots, total_slots, tokens)

        def record_prefill(self, seconds, tokens=1):
            self.stamped["prefill"].append((time.monotonic(), float(seconds)))
            super().record_prefill(seconds, tokens)

        def record_queue_wait(self, seconds, tenant=None):
            self.stamped["queue_wait"].append(
                (time.monotonic(), float(seconds)))
            super().record_queue_wait(seconds, tenant=tenant)

    return StampedMetrics()


def samples_between(metrics, t0: float, t1: float) -> dict:
    def inside(rows, col):
        return [r[col] for r in rows if t0 <= r[0] <= t1]

    s = metrics.stamped
    return {"step_secs": inside(s["step"], 1),
            "occupancy": inside(s["step"], 2),
            "prefill_secs": inside(s["prefill"], 1),
            "queue_wait_secs": inside(s["queue_wait"], 1)}


def warm_lengths(traffic: dict) -> list:
    """Prompt lengths that reach every prefill program the clipped lengths
    can draw, for a program that buckets by powers of two or by any rule
    that changes at them: both ends of the range, and each power of two
    inside it with the length just above."""
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    out, p = {lo, hi}, 1
    while p <= hi:
        out.update(n for n in (p, p + 1) if lo <= n <= hi)
        p *= 2
    return sorted(out)


def offer_load(plan: dict, timeout: float) -> list:
    """Run the load generator's child on ``plan`` and return its records."""
    work = tempfile.mkdtemp(prefix="bench_load_")
    try:
        path = os.path.join(work, "plan.json")
        with open(path, "w") as fp:
            json.dump(plan, fp)
        proc = subprocess.Popen(
            [sys.executable, os.path.join(harness.HERE, "loadgen.py"), path],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise BenchError("the load generator did not end in time")
        if proc.returncode != 0:
            raise BenchError(
                "the load generator failed: " + err.decode()[-2000:])
        return json.loads(out)
    finally:
        shutil.rmtree(work, ignore_errors=True)


class Serving:
    """The system under test, built once: model, weights, server, URL."""

    def __init__(self, cell, seed: int):
        import jax

        from ml_trainer_tpu.serving.api import Server

        self.cell, self.seed = cell, seed
        self.sizes = cell.sizes()
        self.weights = harness.make_weights(cell, seed)
        jax.block_until_ready(self.weights)
        self.metrics = _stamped_metrics()
        self.options = dict(cell.config["program"]["server_options"])
        self.slots = int(self.options["max_batch"])
        self.server = Server(harness.build_model(cell.config),
                             {"params": self.weights},
                             metrics=self.metrics, **self.options)
        self.host, self.port = self.server.serve_http(port=0)

    def plan(self, **kw) -> dict:
        return {"host": self.host, "port": self.port,
                "vocab": self.sizes["vocab"], "seed": self.seed, **kw}

    def warm_up(self) -> None:
        """One request for each prefill program the mix can reach, two
        tokens each, through the route the window uses."""
        rng = np.random.default_rng([self.seed, 0x3A23])
        reqs = [{"id": i, "due": None, "max_new_tokens": 2,
                 "prompt": rng.integers(0, self.sizes["vocab"],
                                        size=n).tolist()}
                for i, n in enumerate(warm_lengths(self.cell.traffic))]
        now = time.monotonic()
        records = offer_load(self.plan(
            loop="list", requests=reqs, start_at=now, end_at=now + 3600.0),
            timeout=3000.0)
        bad = [r for r in records if r["status"] != "ok"]
        if bad or len(records) != len(reqs):
            raise BenchError(f"warm-up failed: {bad[:2]}")

    def close(self) -> None:
        """Stop the server and free its device state (weights stay)."""
        self.server.close()
        self.server.engine.cache = None
        self.server.engine.tok = None
        self.server = None
        gc.collect()


def traced_slice(seconds: float) -> dict:
    """Trace ``seconds`` of the running system, in normal form."""
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        harness.start_trace(log_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return trace_reduce.load_xplane(trace_reduce.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def check_outputs(cell, weights, sizes: dict, records: list, seed: int,
                  lower: str = None) -> dict:
    """Once the window has closed: a sample of the finished requests, drawn
    from the seed with the longest in it; one reference pass over each
    prompt with its served tokens; for every served token, how far its
    reference logit lies below the reference's best.

    Compared: the MEAN of those gaps over the sample.  The widest gap is
    reported beside it and held to nothing: it is the largest near-tie that
    rounding happened to flip among a thousand tokens, and over 19 seeds
    the program's bfloat16 reached 0.044 where the fp8 control's smallest
    reading was 0.056 (PERF.md section 2), so no limit separates them.

    ``lower`` puts the control in the program's place: at each position of
    the same prompts and tokens, the token that the reference computed in
    that precision ('fp8') puts first is judged instead of the served one."""
    done = [r for r in records if r["status"] == "ok" and r["tokens"]]
    pool = done or [r for r in records if r["tokens"]]
    short = sum(1 for r in done if len(r["tokens"]) != r["max_new_tokens"])
    limits = cell.config["limits"]
    compared = {
        "short_replies": {"value": short, "limit": 0},
        "served_token_gap_mean": {
            "value": None, "limit": limits["served_token_gap_mean"]},
    }
    if not pool:
        return {"compared": compared, "tokens_checked": 0}
    k = int(cell.config["check"]["requests"])
    longest = max(pool, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in pool if r is not longest]
    pick = np.random.default_rng([seed, 0xC0DE]).permutation(len(rest))
    sample = [longest] + [rest[i] for i in pick[:k - 1]]
    wanted = {r["id"] for r in sample}
    prompts = {}
    for req in loadgen.iter_schedule(cell.traffic, sizes["vocab"], seed):
        if req["id"] in wanted:
            prompts[req["id"]] = req["prompt"]
        if req["id"] >= max(wanted):
            break
    gaps = np.concatenate([
        cell.reference.served_token_gaps(
            weights, sizes, prompts[r["id"]], r["tokens"], lower=lower)
        for r in sample])
    compared["served_token_gap_mean"]["value"] = float(gaps.mean())
    return {"compared": compared, "tokens_checked": int(gaps.size),
            "requests_checked": len(sample),
            "widest_gap": float(gaps.max()),
            "tokens_not_best": int((gaps > 0).sum())}


def measure(sv: Serving, seconds: float, trace: bool) -> dict:
    """Lead-in, window, and (traced runs) a traced slice after the window
    with the load still on.  Returns the records and the window's edges."""
    traffic = sv.cell.traffic
    start = time.monotonic() + float(traffic.get("child_start_s", 1.0))
    t0 = start + float(traffic["lead_in_s"])
    t1 = t0 + seconds
    trace_s = float(traffic.get("trace_s", 2.0)) if trace else 0.0
    end = t1 + (trace_s + 3.0 if trace else 0.0)
    plan = sv.plan(loop=traffic["loop"], traffic=traffic, start_at=start,
                   end_at=end, first_token_wait_s=loadgen.MISS_MS / 1e3,
                   clients=(loadgen.n_clients(traffic, sv.slots)
                            if traffic["loop"] == "closed" else 0))
    result = {}

    def side_work():
        if trace:
            time.sleep(max(0.0, t1 + 0.5 - time.monotonic()))
            result["trace"] = traced_slice(trace_s)

    side = threading.Thread(target=side_work, name="bench-trace")
    side.start()
    try:
        records = offer_load(plan, timeout=(end - time.monotonic()) + 150.0)
    finally:
        side.join()
    return {"records": records, "t0": t0, "t1": t1, **result}


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        allow_cpu: bool = False) -> dict:
    facts = harness.device_facts(cell.chips, allow_cpu)
    if not allow_cpu:
        harness.use_compile_cache()
    counter = harness.CompileCounter()
    harness.phase("imports_and_device", t_process)
    sv = Serving(cell, seed)
    harness.phase("weights_and_server", t_process)
    try:
        sv.warm_up()
        harness.phase("warm_up", t_process)
        got = measure(sv, seconds, trace)
        harness.phase("window_closed", t_process)
        t0, t1 = got["t0"], got["t1"]
        harness.forbid_compiles(counter, t0, t1)
        samples = samples_between(sv.metrics, t0, t1)
        errors = int(sv.metrics.engine_errors)
        peak = harness.memory_peak_bytes()
    finally:
        weights, sizes, slots = sv.weights, sv.sizes, sv.slots
        sv.close()
    records = got["records"]
    client = loadgen.client_stats(records, t0, t1, cell.traffic["loop"])
    checked = check_outputs(cell, weights, sizes, records, seed)
    harness.phase("outputs_checked", t_process)
    compared = checked["compared"]
    print("checked: " + json.dumps(
        {k: v for k, v in checked.items() if k != "compared"}),
        file=sys.stderr)
    compared["failed_requests"] = {
        "value": client["failed"] + errors, "limit": 0}
    numbers = dict(client)
    numbers["setup_s"] = t0 - t_process
    ctx = {"sizes": sizes, "slots": slots, "window": (t0, t1),
           "client": client, "records": records, "samples": samples,
           "trace": got.get("trace")}
    return harness.finish(cell, trace, facts, numbers, compared,
                          client["attempted"], client["failed"], peak, ctx,
                          allow_cpu)
