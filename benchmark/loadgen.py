"""Traffic: the schedule from a seed, the clients, and the client's metrics.

A mended copy of ``ml_trainer_tpu/serving/loadgen.py`` (schedule fixed
before the run, send lag reported, host only), with its three faults for a
benchmark repaired: lengths are heavy-tailed (clipped lognormal), latency
runs from when a request was DUE and not from when it was sent, and the
clients speak ``POST /v1/stream``, whose reply comes a token a line, so the
first token and every gap are read on the client's clock.

No JAX here, by design: ``python benchmark/loadgen.py <plan.json>`` is the
child process that offers the load, so that the clients never contend for
the interpreter lock of the process that holds the chip.  Parent and child
share ``time.monotonic()`` (CLOCK_MONOTONIC is system-wide on Linux).

Every seed offers the same sizes in another order: the lengths are the
stratified quantiles of their distributions, a fixed multiset of ``block``
pairs that each consecutive block of requests permutes anew from the seed.
In an open loop the arrivals are a Poisson process drawn from the seed:
independent exponential gaps at ``rate_rps``, bursts and lulls included.
"""

from __future__ import annotations

import asyncio
import json
import math
import statistics
import sys
import time

import numpy as np

MISS_MS = 60_000.0  # a refused or failed request's latency: the longest wait


# ---------------------------------------------------------------- schedule
def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.asarray([nd.inv_cdf(float(q)) for q in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "fixed":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def base_block(traffic: dict) -> dict:
    """The fixed multiset every block of requests permutes: paired prompt
    and output lengths, the output cut to what ``max_total`` leaves."""
    n = int(traffic["block"])
    pairing = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    prompts = _quantiles(traffic["prompt_len"], n)
    outputs = _quantiles(traffic["output_len"], n)[pairing.permutation(n)]
    outputs = np.minimum(outputs, int(traffic["max_total"]) - prompts)
    if outputs.min() < 1:
        raise ValueError("a prompt leaves no room for an output token")
    return {"prompt_len": prompts, "output_len": outputs}


def iter_schedule(traffic: dict, vocab: int, seed: int):
    """The requests in order of issue, without end: prompt token ids, output
    budget and, in an open loop, the due time in seconds from the start of
    the lead-in.  The same seed gives the same bytes, so the child that
    sends them and the parent that checks them each make their own."""
    base = base_block(traffic)
    n = int(traffic["block"])
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    arrivals = np.random.default_rng([int(seed), 0xA221])
    open_loop = traffic["loop"] == "open"
    if open_loop and traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    issued, due = 0, 0.0
    while True:
        for i in rng.permutation(n):
            if open_loop:
                due += float(arrivals.exponential(1.0 / traffic["rate_rps"]))
            yield {
                "id": issued,
                "prompt": rng.integers(
                    0, vocab, size=int(base["prompt_len"][i])).tolist(),
                "max_new_tokens": int(base["output_len"][i]),
                "due": due if open_loop else None,
            }
            issued += 1


def n_clients(traffic: dict, slots: int) -> int:
    return int(round(float(traffic["clients_per_slot"]) * slots))


# ----------------------------------------------------------------- clients
async def _stream_one(host: str, port: int, req: dict, rec: dict) -> None:
    """One request over ``POST /v1/stream``: the first NDJSON line is the
    admission verdict, then a line a token, then ``done``."""
    rec["sent"] = time.monotonic()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({"id": req["id"], "prompt": req["prompt"],
                           "max_new_tokens": req["max_new_tokens"],
                           "temperature": 0.0}).encode()
        writer.write(
            b"POST /v1/stream HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nConnection: close\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            rec["status"] = "error"
            rec["error"] = status.decode(errors="replace").strip()
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            line = await reader.readline()
            now = time.monotonic()
            if not line:
                rec["status"] = "error"
                rec["error"] = "stream ended without done"
                return
            obj = json.loads(line)
            if "t" in obj:
                rec["tokens"].append(int(obj["t"]))
                rec["times"].append(now)
            elif "status" in obj:
                if obj["status"] != "accepted":
                    rec["status"] = "refused"
                    rec["error"] = str(obj.get("error", obj["status"]))
                    return
            elif "done" in obj:
                state = obj["done"].get("state")
                rec["status"] = "ok" if state == "done" else "error"
                if state != "done":
                    rec["error"] = str(obj["done"].get("error", state))
                return
    except asyncio.CancelledError:
        rec["status"] = "cut"  # still running when the run let go of it
        raise
    except (OSError, ValueError) as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


def _new_record(req: dict, due_abs) -> dict:
    return {"id": req["id"], "prompt_len": len(req["prompt"]),
            "max_new_tokens": req["max_new_tokens"], "due": due_abs,
            "sent": None, "status": "pending", "error": None,
            "tokens": [], "times": []}


async def _run(plan: dict) -> list:
    host, port = plan["host"], int(plan["port"])
    start, end = float(plan["start_at"]), float(plan["end_at"])
    records, tasks = [], []
    if plan["loop"] == "list":  # warm-up: explicit requests, one at a time
        for req in plan["requests"]:
            records.append(_new_record(req, None))
            await _stream_one(host, port, req, records[-1])
        return records
    reqs = iter_schedule(plan["traffic"], int(plan["vocab"]),
                         int(plan["seed"]))

    async def sleep_until(t):
        while True:
            left = t - time.monotonic()
            if left <= 0:
                return
            await asyncio.sleep(min(left, 0.5))

    await sleep_until(start)
    if plan["loop"] == "closed":
        async def client():
            while time.monotonic() < end:
                req = next(reqs)
                rec = _new_record(req, None)
                records.append(rec)
                await _stream_one(host, port, req, rec)
                if rec["status"] != "ok":  # a refusal must not spin
                    await asyncio.sleep(0.05)

        tasks = [asyncio.ensure_future(client())
                 for _ in range(int(plan["clients"]))]
        await sleep_until(end)
    else:
        for req in reqs:
            due = start + req["due"]
            if due >= end:
                break
            await sleep_until(due)
            rec = _new_record(req, due)
            records.append(rec)
            tasks.append(asyncio.ensure_future(
                _stream_one(host, port, req, rec)))
        await sleep_until(end)
        # A late first token is late, not missing: wait for it.
        limit = end + float(plan["first_token_wait_s"])
        while time.monotonic() < limit and any(
                r["status"] == "pending" and not r["tokens"] for r in records):
            await asyncio.sleep(0.05)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for r in records:
        if r["status"] == "pending":
            r["status"] = "cut"
    return records


def child_main(argv) -> int:
    with open(argv[1]) as fp:
        plan = json.load(fp)
    records = asyncio.run(_run(plan))
    json.dump(records, sys.stdout)
    sys.stdout.flush()
    return 0


# ----------------------------------------------------------------- metrics
def percentile(values, q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample at or
    below it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    return float(vals[max(0, math.ceil(q * len(vals)) - 1)])


def client_stats(records: list, t0: float, t1: float, loop: str) -> dict:
    """What the clients saw of the window [t0, t1]: every token received
    in it over its whole length, every gap between two tokens of one
    request that both arrived in it, and the time to first token of every
    request that fell due in it (open loop: from the due time; closed loop:
    from the send).  A refused or failed request misses."""
    tokens, gaps, ttft, lag = 0, [], [], []
    attempted = failed = 0
    for r in records:
        times = r["times"]
        inside = [t for t in times if t0 <= t <= t1]
        tokens += len(inside)
        gaps.extend(b - a for a, b in zip(inside, inside[1:]))
        origin = r["due"] if loop == "open" else r["sent"]
        if origin is None or not (t0 <= origin < t1):
            continue
        attempted += 1
        bad = r["status"] in ("refused", "error")
        failed += bad
        if r["sent"] is not None and r["due"] is not None:
            lag.append((r["sent"] - r["due"]) * 1e3)
        if times and not bad:
            ttft.append((times[0] - origin) * 1e3)
        elif bad or loop == "open":
            ttft.append(MISS_MS)
    stats = {"attempted": attempted, "failed": failed, "tokens": tokens,
             "gaps": len(gaps), "window_s": t1 - t0,
             "serve_tokens_per_s": tokens / (t1 - t0)}
    if gaps:
        stats["itl_p95_ms"] = percentile(gaps, 0.95) * 1e3
    if ttft:
        stats["ttft_p95_ms"] = percentile(ttft, 0.95)
        stats["ttft_p50_ms"] = percentile(ttft, 0.50)
    if lag:
        stats["send_lag_p95_ms"] = percentile(lag, 0.95)
    return stats


if __name__ == "__main__":
    sys.exit(child_main(sys.argv))
